"""dither_zero_share: the share of zero levels in the quantized cotangents
of every kernel-variant projection of the window's last step, weighted by
their elements, in %: the paper's Table-1 sparsity. Read from the program's
counter (``chipbench/tally.py``)."""
from chipbench import tally


def read(ctx):
    row = tally.last()
    if row is None or row["elements"] == 0:
        return None
    return 100.0 * row["zeros"] / row["elements"]
