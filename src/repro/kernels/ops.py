"""High-level jit'd wrappers over the Pallas kernels.

``dithered_backward_matmuls`` is the full TPU-native backward pass of one
dense layer (DESIGN.md §4): one fused NSD pass over the pre-activation
gradient, then both backward products as tile-skipping quantized matmuls.
The pipeline shares ONE occupancy representation with the wire format and
the residual store:

    fused NSD kernel  ->  int8 k + per-tile nnz map      (no second pass)
    pack kernel       ->  uint8 occupancy bitmap          (wire layout)
    tile mask         ->  popcount-style reduction of the BITMAP
                          (repro.comm.wireformat.tile_mask_from_bitmap) —
                          never a dense recompute over the int8 tensor

Non-128-aligned layers are zero-padded to tile multiples: padded elements
quantize to k == 0, so the padding tiles read 0 in the mask and are skipped
for free (no silent dense fallback remains — structural fallbacks that do
survive, e.g. unsupported einsum forms, are counted in
``KERNEL_FALLBACKS``). ``interpret=None`` resolves backend-aware: interpret
off-TPU, compiled on TPU (``repro.kernels.backend``).

Each pass runs under a named scope, so a device profile can attribute the
backward's time: ``noise`` (Delta, the dither draw, padding), ``nsd`` (the
fused quantize kernel), ``pack`` (bitmap and tile mask) and ``matmul``
(operand absmax, transposes and both products). ``repro.core.dithered``
wraps them in ``dither/bwd``.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import nsd
from repro.kernels.backend import default_interpret
from repro.kernels.bsp_matmul.bsp_matmul import bsp_matmul, bsp_matmul_int8
from repro.kernels.nsd_quant.nsd_quant import nsd_quantize_blocked
from repro.kernels.pack.pack import bitmap_pack_blocked
from repro.obs.trace import annotate
from repro.quant import wire as wireformat
from repro.quant.codecs import absmax_int8

# Trace-time counter of structural kernel-path fallbacks (unsupported
# einsum form, grouped/dilated conv, ...). Keyed by reason string; tests
# assert a fallback is COUNTED, never silent. Shape misalignment is not a
# reason anymore — padding handles it.
KERNEL_FALLBACKS: dict = {}


def note_fallback(reason: str, name: str) -> None:
    KERNEL_FALLBACKS[reason] = KERNEL_FALLBACKS.get(reason, 0) + 1


def _pad_to(x: jax.Array, m: int, n: int) -> jax.Array:
    M, N = x.shape
    pm, pn = (-M) % m, (-N) % n
    if pm or pn:
        x = jnp.pad(x, ((0, pm), (0, pn)))
    return x


class QuantizedGrad(NamedTuple):
    """A pre-activation gradient after the fused NSD pass, tile-mask ready.

    ``k`` is zero-padded to ``block`` multiples; ``nnz`` is the fused
    kernel's per-tile non-zero map (NOT recomputed from ``k``); ``bitmap``
    is the packed wire-format occupancy; ``mask`` is the tile mask the
    matmul kernels consume, derived from ``bitmap``. ``shape`` is the
    unpadded (M, N).
    """

    k: jax.Array  # (Mp, Np) int8
    delta: jax.Array  # f32 scalar
    nnz: jax.Array  # (Mp/block, Np/block) int32, from the fused NSD kernel
    bitmap: jax.Array  # (Mp, Np//8) uint8 packed occupancy
    mask: jax.Array  # (Mp/block, Np/block) int32, derived from ``bitmap``
    shape: Tuple[int, int]


def nsd_quantize_kernel(g: jax.Array, key: jax.Array, s, *,
                        bm: int = 128, bn: int = 512,
                        interpret: Optional[bool] = None):
    """NSD via the Pallas kernel. g: (M, N). Returns (k, delta, nnz_map).

    delta/std are global reductions (outside the kernel); dither noise comes
    from the framework RNG so results are bit-identical to repro.core.nsd
    given the same key. ``k`` is sliced back to the input shape; ``nnz``
    covers the padded tile grid (padding tiles are all-zero).
    """
    interpret = default_interpret(interpret)
    M, N = g.shape
    with annotate("noise"):
        delta = nsd.compute_delta(g, s)
        noise = nsd.dither_noise(key, g.shape, delta)
        gp = _pad_to(g, bm, bn)
        np_ = _pad_to(noise, bm, bn)
    with annotate("nsd"):
        k, nnz = nsd_quantize_blocked(gp, np_, delta, bm=bm, bn=bn,
                                      interpret=interpret)
    return k[:M, :N], delta, nnz


def quantize_and_mask(g: jax.Array, key: jax.Array, s, *,
                      block: int = 128,
                      interpret: Optional[bool] = None) -> QuantizedGrad:
    """Fused NSD quantize + bitmap pack + bitmap-derived tile mask.

    One NSD pass produces the int8 payload and the per-tile nnz map; one
    pack pass produces the wire-format bitmap; the tile mask the matmul
    kernels consume comes from the bitmap (popcount-style reduction), so
    wire, residual store and backward compute share one representation.
    ``mask`` equals ``(nnz > 0)`` bit-exactly (pinned in tests).
    """
    interpret = default_interpret(interpret)
    M, N = g.shape
    with annotate("noise"):
        delta = nsd.compute_delta(g, s)
        noise = nsd.dither_noise(key, g.shape, delta)
        gp = _pad_to(g, block, block)
        np_ = _pad_to(noise, block, block)
    with annotate("nsd"):
        k, nnz = nsd_quantize_blocked(gp, np_, delta, bm=block, bn=block,
                                      interpret=interpret)
    with annotate("pack"):
        bitmap, _ = bitmap_pack_blocked(k, bm=block, bn=block,
                                        interpret=interpret)
        mask = wireformat.tile_mask_from_bitmap(bitmap, block, block)
    return QuantizedGrad(k=k, delta=delta, nnz=nnz, bitmap=bitmap,
                         mask=mask, shape=(M, N))


def quantized_from_indices(k: jax.Array, delta: jax.Array, *,
                           block: int = 128,
                           interpret: Optional[bool] = None) -> QuantizedGrad:
    """Build a :class:`QuantizedGrad` from precomputed NSD indices.

    For callers that already hold the int8 k tensor (an einsum slice of a
    jointly-quantized gradient, a gradient that arrived in wire format):
    pads, packs the bitmap, and derives the tile mask + per-tile nnz from
    the bitmap alone — no dense recompute.
    """
    interpret = default_interpret(interpret)
    M, N = k.shape
    with annotate("pack"):
        kp = _pad_to(k.astype(jnp.int8), block, block)
        bitmap, _ = bitmap_pack_blocked(kp, bm=block, bn=block,
                                        interpret=interpret)
        mask = wireformat.tile_mask_from_bitmap(bitmap, block, block)
        nnz = wireformat.tile_nnz_from_bitmap(bitmap, block, block)
    return QuantizedGrad(k=kp, delta=delta, nnz=nnz, bitmap=bitmap,
                         mask=mask, shape=(M, N))


def bsp_backward_from_quantized(
    q: QuantizedGrad, x: jax.Array, w: jax.Array, *, block: int = 128,
    int8_operands: bool = True, interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Both backward products of y = x @ w from a quantized cotangent.

    q.k plays g~ (T, N) zero-padded; x: (..., K) reshaped to (T, K);
    w: (K, N). Returns (dx (T, K), dw (K, N)); operands are zero-padded to
    tile multiples and outputs sliced back, so any layer shape takes the
    tile-skipping kernel path.
    """
    interpret = default_interpret(interpret)
    T, N = q.shape
    K = x.shape[-1]
    x2d = x.reshape(-1, K)
    with annotate("matmul"):
        if int8_operands:
            wq = absmax_int8(w)
            xq = absmax_int8(x2d)
            # dx = g~ @ w^T : tiles of g~ index rows; mask transposes with g~
            dx = bsp_matmul_int8(
                q.k, _pad_to(wq.q.T, block, block), q.delta * wq.scale,
                q.mask, bm=block, bk=block, bn=block, interpret=interpret)
            # dw = x^T @ g~ = (g~^T @ x)^T; mask for g~^T is mask^T
            dw_t = bsp_matmul_int8(
                q.k.T, _pad_to(xq.q, block, block), q.delta * xq.scale,
                q.mask.T, bm=block, bk=block, bn=block, interpret=interpret)
        else:
            dx = bsp_matmul(q.k, q.delta,
                            _pad_to(w.T.astype(jnp.float32), block, block),
                            q.mask, bm=block, bk=block, bn=block,
                            interpret=interpret)
            dw_t = bsp_matmul(q.k.T, q.delta,
                              _pad_to(x2d, block, block).astype(jnp.float32),
                              q.mask.T, bm=block, bk=block, bn=block,
                              interpret=interpret)
        return (dx[:T, :K].astype(x.dtype),
                dw_t[:N, :K].T.astype(w.dtype))


def dithered_backward_matmuls(
    g: jax.Array, x: jax.Array, w: jax.Array, key: jax.Array, s, *,
    block: int = 128, int8_operands: bool = True,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """TPU-native backward for y = x @ w given cotangent g.

    g: (T, N) pre-activation gradient; x: (T, K); w: (K, N) — any shapes
    (zero-padded to tile multiples internally). Returns (dx (T, K),
    dw (K, N)) using the fused NSD kernel + the tile-skipping quantized
    matmul kernels, with the tile mask derived from the packed bitmap.
    """
    q = quantize_and_mask(g, key, s, block=block, interpret=interpret)
    return bsp_backward_from_quantized(q, x, w, block=block,
                                       int8_operands=int8_operands,
                                       interpret=interpret)
