"""dither_kernels_ms: device time per step of the dithered backward's
kernels, found by their names in the trace, in ms."""

KERNELS = ("nsd_quantize_blocked", "bitmap_pack_blocked", "bsp_matmul_int8")


def read(ctx):
    t = ctx["trace"].matching_s(lambda op: any(k in op.name for k in KERNELS))
    if t == 0.0:
        return None
    return 1e3 * t / ctx["out"]["steps"]
