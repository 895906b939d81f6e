"""tokens_per_s: tokens of every step completed in the window, over the
window's wall time on the host clock."""


def read(ctx):
    out = ctx["out"]
    return out["tokens"] / out["window_s"]
