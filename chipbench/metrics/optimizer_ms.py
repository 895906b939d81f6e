"""optimizer_ms: device time per step of the optimizer update, the ops under
the trainer's ``step/update`` scope (clipping and AdamW), in ms."""
from chipbench import scopes


def read(ctx):
    return scopes.per_step_ms(ctx, "step/update")
