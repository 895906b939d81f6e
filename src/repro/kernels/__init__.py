"""Pallas TPU kernels for the paper's hot spots (+ pure-jnp oracles).

backend.py   backend-aware interpret default (interpret off-TPU, compiled on)
nsd_quant/   fused NSD quantize -> (int8 k, tile-occupancy map)
bsp_matmul/  tile-masked quantized matmuls (dequant + full-int8 variants;
             a masked tile contributes nothing, and a compute block with no
             live tile skips MXU issue AND operand DMA via fetch maps; the
             int8 variant sizes its blocks from the shapes, the dequant
             one computes 128 x 128 tiles)
pack/        occupancy-bitmap pack/unpack for the comm wire format
levels/      chunk-local compact/expand of the wire's non-zero int8 levels
             (butterfly routing network; replaces the jnp full-cumsum
             compact behind repro.quant.wire's pallas backend)
ops.py       jit'd high-level wrappers: the full dithered backward pipeline
             (fused NSD -> wire bitmap -> bitmap-derived tile mask ->
             tile-skipping backward products) for any layer shape
"""
from repro.kernels.backend import default_interpret, on_tpu
from repro.kernels.nsd_quant.nsd_quant import nsd_quantize_blocked
from repro.kernels.bsp_matmul.bsp_matmul import (bsp_matmul, bsp_matmul_int8,
                                                 fetch_map)
from repro.kernels.pack.pack import bitmap_pack_blocked, bitmap_unpack_blocked
from repro.kernels.levels.levels import (levels_compact_blocked,
                                         levels_expand_blocked)
from repro.kernels import ops

__all__ = ["default_interpret", "on_tpu", "nsd_quantize_blocked",
           "bsp_matmul", "bsp_matmul_int8", "fetch_map",
           "bitmap_pack_blocked", "bitmap_unpack_blocked",
           "levels_compact_blocked", "levels_expand_blocked", "ops"]
