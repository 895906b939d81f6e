"""head_ms: device time per step of the LM head, the ops under the
program's ``head`` scope (final norm, tied unembedding, log-softmax and NLL,
forward and backward), in ms."""
from chipbench import scopes


def read(ctx):
    return scopes.per_step_ms(ctx, "head")
