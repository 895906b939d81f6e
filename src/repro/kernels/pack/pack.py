"""Occupancy-bitmap pack/unpack kernels (Pallas, TPU-native layout).

The wire format of ``repro.comm.wireformat`` sends one occupancy bit per
gradient element plus the non-zero int8 levels. Producing that bitmap is a
pure bandwidth problem — one pass over the int8 index tensor the fused NSD
kernel already emits — so it belongs in the same kernel family:

    pack:   per (bm, bn) VMEM tile of int8 k ->
                bitmap tile (bm, bn/8) uint8 (LSB-first within each byte)
                nnz       (int32)  per-tile non-zero count (wire accounting)
    unpack: bitmap tile -> int8 0/1 occupancy mask tile (bm, bn)

Bit order matches ``wireformat.pack_bitmap`` (bit j of byte b is element
8*b + j of the row).

Layout: Mosaic cannot lower a reshape that regroups the minor (lane)
dimension, which is what the obvious ``(bm, bn) -> (bm, bn/8, 8)`` byte
gather needs. The kernels therefore run on the TRANSPOSED tile so the 8
elements of each wire byte lie along the *sublane* dimension, where
grouping is free:

    1. weight each sublane's occupancy bit by its position in the byte
       (``bit << (sublane & 7)``),
    2. OR-reduce runs of 8 sublanes with a log-tree of circular sublane
       rotates (``pltpu.roll`` by bn-1, bn-2, bn-4), after which every
       sublane s ≡ 0 (mod 8) holds the finished byte for elements s..s+7,
    3. select those sublanes via the lane-preserving reshape
       ``(bn, bm) -> (bn/8, 8, bm)`` and a sublane index — physically a
       no-op regrouping Mosaic lowers directly.

The host-side wrappers feed the kernel ``k.T`` and transpose the bitmap
back, so the public API (shapes, bit order, nnz map) is unchanged; the
transposes are plain XLA ops outside ``pallas_call``. No reshape anywhere
in the kernel bodies touches the minor dimension —
``tests/test_pack_layout.py`` asserts that on the traced jaxpr. Tiles are
(8m, 128)-aligned as for the other kernels; bn must additionally be a
multiple of 8 (always true for 128-lane tiles).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import default_interpret, write_tile_count


def _pack_kernel(kt_ref, bitmap_ref, nnz_ref):
    kt = kt_ref[...]  # (bn, bm): transposed tile, wire bytes along sublanes
    bn, bm = kt.shape
    bits = (kt != 0).astype(jnp.int32)
    sub = jax.lax.broadcasted_iota(jnp.int32, (bn, bm), 0)
    acc = bits << (sub & 7)  # bit weight 2^(s mod 8) per sublane
    # OR-tree over runs of 8 sublanes; rolls are circular and the wrap
    # never crosses a byte boundary at the s % 8 == 0 sublanes we keep.
    acc = acc | pltpu.roll(acc, bn - 1, 0)
    acc = acc | pltpu.roll(acc, bn - 2, 0)
    acc = acc | pltpu.roll(acc, bn - 4, 0)
    bitmap_ref[...] = acc.reshape(bn // 8, 8, bm)[:, 0, :].astype(jnp.uint8)
    write_tile_count(nnz_ref, pl.program_id(1), jnp.sum(bits))


def _unpack_kernel(bitmap_ref, mask_ref):
    bt = bitmap_ref[...].astype(jnp.int32)  # (bn/8, bm): transposed bitmap
    bnb, bm = bt.shape
    # replicate each byte across its 8 target sublanes (lane-preserving
    # broadcast + collapse), then select each sublane's bit
    rep = jnp.broadcast_to(bt[:, None, :], (bnb, 8, bm)).reshape(bnb * 8, bm)
    sub = jax.lax.broadcasted_iota(jnp.int32, (bnb * 8, bm), 0)
    mask_ref[...] = ((rep >> (sub & 7)) & 1).astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def bitmap_pack_blocked(k: jax.Array, *, bm: int = 128, bn: int = 128,
                        interpret: Optional[bool] = None):
    """k: (M, N) int8 with M % bm == 0, N % bn == 0, bn % 8 == 0.

    Returns (bitmap uint8 (M, N//8), nnz int32 (M//bm, N//bn)).
    """
    interpret = default_interpret(interpret)
    M, N = k.shape
    assert M % bm == 0 and N % bn == 0 and bn % 8 == 0, (k.shape, bm, bn)
    grid = (N // bn, M // bm)
    # nnz is gathered per column of tiles (the inner grid axis walks rows)
    # into a resident (1, M // bm) count row; see nsd_quant for why
    bitmap_t, nnz_cols = pl.pallas_call(
        _pack_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((bn, bm), lambda j, i: (j, i))],
        out_specs=[
            pl.BlockSpec((bn // 8, bm), lambda j, i: (j, i)),
            pl.BlockSpec((None, 1, M // bm), lambda j, i: (j, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N // 8, M), jnp.uint8),
            jax.ShapeDtypeStruct((N // bn, 1, M // bm), jnp.int32),
        ],
        interpret=interpret,
    )(k.T)
    return bitmap_t.T, nnz_cols[:, 0, :].T


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def bitmap_unpack_blocked(bitmap: jax.Array, *, bm: int = 128, bn: int = 128,
                          interpret: Optional[bool] = None) -> jax.Array:
    """bitmap: (M, N//8) uint8 -> int8 0/1 occupancy mask (M, N)."""
    interpret = default_interpret(interpret)
    M, NB = bitmap.shape
    N = NB * 8
    assert M % bm == 0 and N % bn == 0 and bn % 8 == 0, (bitmap.shape, bm, bn)
    grid = (N // bn, M // bm)
    mask_t = pl.pallas_call(
        _unpack_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((bn // 8, bm), lambda j, i: (j, i))],
        out_specs=pl.BlockSpec((bn, bm), lambda j, i: (j, i)),
        out_shape=jax.ShapeDtypeStruct((N, M), jnp.int8),
        interpret=interpret,
    )(bitmap.T)
    return mask_t.T
