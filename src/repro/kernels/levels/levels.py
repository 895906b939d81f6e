"""Chunk-local levels compact/expand kernels (Pallas, TPU-native layout).

The wire format (``repro.quant.wire``) stores the non-zero int8 levels of
each tensor compacted to the front in flat order. The jnp reference does
that with a full-length ``cumsum`` + scatter per encode — an O(n) serial
dependence over the whole tensor. These kernels replace the element-level
cumsum with a *chunk-local* compact: each wire chunk (256 elements) is
compacted independently inside VMEM, and the host-side assembly only
cumsums the per-chunk counts (n/256x shorter) before one scatter.

Layout follows ``repro.kernels.pack``: the tile is TRANSPOSED so the chunk
lies along the *sublane* axis (256 sublanes) and 128 chunks ride the lanes;
all data movement inside a chunk is then circular sublane rotation
(``pltpu.roll``), which Mosaic lowers natively — no gather, no minor-dim
reshape anywhere in the kernel bodies.

The compact itself is a butterfly permutation network. Each non-zero at row
``j`` must move LEFT (toward row 0) by ``rem = j - P[j]`` where ``P[j]``
counts the non-zeros in rows ``< j`` (one strictly-lower-triangular 256x256
matmul — exact in f32, counts <= 256). Eight LSB-first rounds then route
every survivor by one bit of its displacement: in round ``b`` the elements
whose remaining displacement has bit ``b`` set hop ``2^b`` rows up. This is
collision-free: after rounds ``< b`` every remaining displacement is a
multiple of ``2^b``, displacements are non-decreasing in ``j`` (ranks
``j - rem`` are strictly increasing and rounds preserve element order), so
a stayer and a hopper meeting at one row would need two elements with the
same final rank — impossible.

``expand`` is the inverse: the per-slot rightward displacement ``r[i]``
(distance from compacted slot ``i`` to the row of the i-th set mask bit)
is itself obtained by forward-compacting the displacement field, then eight
MSB-first rounds route the levels RIGHT. MSB-first is load-bearing —
rightward LSB-first can collide (mask 0101 routes both slots through row 1
in round 0); descending bit order keeps intermediate targets distinct.

Both kernels are bit-exact vs ``repro.quant.wire._compact``/``_expand``
composition in interpret mode for every shape, including all-zero and
all-nonzero chunks (tests/test_levels_kernel.py); tests/test_tpu_compile.py
compiles them for a described TPU chip.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import default_interpret

CHUNK = 256  # the one supported chunk length (== wire DEFAULT_CHUNK)


def _prefix_counts(occ: jax.Array) -> jax.Array:
    """P[j, c] = number of occupied rows < j in column c (int32, exact).

    One (L, L) @ (L, bm) strictly-lower-triangular matmul on the MXU; f32
    accumulation is exact for counts <= 256.
    """
    L = occ.shape[0]
    j = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    i = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    sl = (i < j).astype(jnp.float32)
    p = jax.lax.dot_general(sl, occ.astype(jnp.float32),
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    return p.astype(jnp.int32)


def _route_left(cur: jax.Array, rem: jax.Array, act: jax.Array
                ) -> Tuple[jax.Array, jax.Array]:
    """Move active elements UP by their displacement, LSB-first.

    ``cur``/``rem``/``act``: (L, bm) int32 values / remaining displacement /
    0-1 activity. Returns (routed values, final activity); inactive rows 0.
    """
    L = cur.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, cur.shape, 0)
    for b in range(8):
        sh = 1 << b
        cur_s = pltpu.roll(cur, L - sh, 0)  # cur_s[j] = cur[j + sh (mod L)]
        rem_s = pltpu.roll(rem, L - sh, 0)
        act_s = pltpu.roll(act, L - sh, 0)
        take = (act_s == 1) & ((rem_s & sh) != 0) & (rows < L - sh)
        keep = (act == 1) & ((rem & sh) == 0)
        cur = jnp.where(take, cur_s, jnp.where(keep, cur, 0))
        rem = jnp.where(take, rem_s - sh, rem)
        act = (take | keep).astype(jnp.int32)
    return cur, act


def _route_right(cur: jax.Array, rem: jax.Array, act: jax.Array
                 ) -> jax.Array:
    """Move active elements DOWN by their displacement, MSB-first."""
    L = cur.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, cur.shape, 0)
    for b in reversed(range(8)):
        sh = 1 << b
        cur_s = pltpu.roll(cur, sh, 0)  # cur_s[j] = cur[j - sh (mod L)]
        rem_s = pltpu.roll(rem, sh, 0)
        act_s = pltpu.roll(act, sh, 0)
        take = (act_s == 1) & ((rem_s & sh) != 0) & (rows >= sh)
        keep = (act == 1) & ((rem & sh) == 0)
        cur = jnp.where(take, cur_s, jnp.where(keep, cur, 0))
        rem = jnp.where(take, rem_s - sh, rem)
        act = (take | keep).astype(jnp.int32)
    return cur


def _compact_kernel(kt_ref, out_ref, cnt_ref):
    kt = kt_ref[...]  # (L, bm) int8: one chunk per lane column
    cur = kt.astype(jnp.int32)
    occ = (cur != 0).astype(jnp.int32)
    p = _prefix_counts(occ)
    rows = jax.lax.broadcasted_iota(jnp.int32, cur.shape, 0)
    routed, _ = _route_left(cur, rows - p, occ)
    out_ref[...] = routed.astype(jnp.int8)
    cnt_ref[...] = jnp.sum(occ, axis=0, keepdims=True)


def _expand_kernel(lv_ref, m_ref, out_ref):
    lv = lv_ref[...].astype(jnp.int32)  # (L, bm) chunk-local compacted
    occ = (m_ref[...] != 0).astype(jnp.int32)  # occupancy mask
    L = lv.shape[0]
    p = _prefix_counts(occ)
    rows = jax.lax.broadcasted_iota(jnp.int32, lv.shape, 0)
    cnt = jnp.sum(occ, axis=0, keepdims=True)  # (1, bm)
    # per-slot rightward displacement = forward-compact of the displacement
    # field d[j] = j - P[j] (# empty rows before the j-th row)
    d = rows - p
    r, _ = _route_left(d, d, occ)
    slot_act = (rows < cnt).astype(jnp.int32)
    routed = _route_right(lv, r, slot_act)
    out_ref[...] = (routed * occ).astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("bm", "interpret"))
def levels_compact_blocked(kt: jax.Array, *, bm: int = 128,
                           interpret: Optional[bool] = None
                           ) -> Tuple[jax.Array, jax.Array]:
    """Column-local stable compaction of (CHUNK, C) int8 chunk columns.

    Returns ``(compacted (CHUNK, C) int8, counts (C,) int32)``: column c of
    the output holds that chunk's non-zeros moved to the front in order,
    zero-padded; ``counts[c]`` is its non-zero count. C is padded to a
    multiple of ``bm`` internally (zero columns compact to zero).
    """
    interpret = default_interpret(interpret)
    L, C = kt.shape
    assert L == CHUNK, (kt.shape, CHUNK)
    pad = (-C) % bm
    if pad:
        kt = jnp.pad(kt, ((0, 0), (0, pad)))
    Cp = C + pad
    out, cnt = pl.pallas_call(
        _compact_kernel,
        grid=(Cp // bm,),
        in_specs=[pl.BlockSpec((L, bm), lambda c: (0, c))],
        out_specs=[pl.BlockSpec((L, bm), lambda c: (0, c)),
                   pl.BlockSpec((1, bm), lambda c: (0, c))],
        out_shape=[jax.ShapeDtypeStruct((L, Cp), jnp.int8),
                   jax.ShapeDtypeStruct((1, Cp), jnp.int32)],
        interpret=interpret,
    )(kt)
    return out[:, :C], cnt[0, :C]


@functools.partial(jax.jit, static_argnames=("bm", "interpret"))
def levels_expand_blocked(lv: jax.Array, mask: jax.Array, *, bm: int = 128,
                          interpret: Optional[bool] = None) -> jax.Array:
    """Inverse of :func:`levels_compact_blocked` given the occupancy mask.

    ``lv``: (CHUNK, C) int8 column-local compacted levels; ``mask``:
    (CHUNK, C) int8/bool occupancy. Returns (CHUNK, C) int8 with each
    column's levels scattered back to its mask positions.
    """
    interpret = default_interpret(interpret)
    L, C = lv.shape
    assert L == CHUNK and mask.shape == lv.shape, (lv.shape, mask.shape)
    pad = (-C) % bm
    if pad:
        lv = jnp.pad(lv, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    Cp = C + pad
    out = pl.pallas_call(
        _expand_kernel,
        grid=(Cp // bm,),
        in_specs=[pl.BlockSpec((L, bm), lambda c: (0, c)),
                  pl.BlockSpec((L, bm), lambda c: (0, c))],
        out_specs=pl.BlockSpec((L, bm), lambda c: (0, c)),
        out_shape=jax.ShapeDtypeStruct((L, Cp), jnp.int8),
        interpret=interpret,
    )(lv, mask.astype(jnp.int8))
    return out[:, :C]
