"""mfu: model FLOPs of the tokens trained in the traced window, over the
window and the bf16 peak of the cell's chips, in %.

The FLOPs per token come from ``flops/<family>.py`` and the configuration
file: dense forward and backward, no remat recompute.
"""


def read(ctx):
    cell, out, trace = ctx["cell"], ctx["out"], ctx["trace"]
    per_token = cell.flops().train_per_token(cell.config)
    rate = out["tokens"] * per_token / trace.window_s()
    return 100.0 * rate / (cell.chips * ctx["peaks"]["bf16_flops"])
