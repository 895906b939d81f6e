"""idle_share: the share of the traced window in which the device ran no
operation, averaged over the cell's devices, in %."""


def read(ctx):
    trace = ctx["trace"]
    return 100.0 * (1.0 - trace.busy_s() / trace.window_s())
