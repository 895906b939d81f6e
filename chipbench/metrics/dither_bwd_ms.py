"""dither_bwd_ms: device time per step of the dithered backward rules, the
ops under the program's ``dither/bwd`` scope (noise, NSD, pack, matmuls,
tally, and the head's paper-variant backward), in ms."""
from chipbench import scopes


def read(ctx):
    return scopes.per_step_ms(ctx, "dither/bwd")
