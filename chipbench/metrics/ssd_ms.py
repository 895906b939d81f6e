"""ssd_ms: device time per step of the Mamba-2 SSD, the ops under the
program's ``mixer/ssd`` scope (forward, remat recompute and backward of the
chunked scan, the D skip and the gated norm), in ms."""
from chipbench import scopes


def read(ctx):
    return scopes.per_step_ms(ctx, "mixer/ssd")
