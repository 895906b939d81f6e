"""dither_kernels_roofline: the least time of the dithered backward of every
dithered projection, over the device time of its kernels, in %.

The least time is bytes over HBM bandwidth: reading the cotangent, the
input and the weight once and writing both gradients once, in the
configuration's dtype (``flops/<family>.py``). The operations needed are
fewer than the bytes at the paper's sparsity, so bytes bound the time.
"""
from chipbench.metrics import dither_kernels_ms


def read(ctx):
    ms = dither_kernels_ms.read(ctx)
    if ms is None:
        return None
    cell = ctx["cell"]
    stream = cell.workload["stream"]
    least = cell.flops().dithered_backward_bytes(
        cell.config, stream["batch"] * stream["seq_len"]) / ctx["peaks"][
            "hbm_bytes_per_s"]
    return 100.0 * least / (ms * 1e-3)
