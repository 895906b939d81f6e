"""Fused NSD quantization kernel (Pallas, TPU target, interpret-validated).

Per (bm, bn) VMEM tile of the pre-activation gradient:
    k     = clip(floor((x + nu)/Delta + 1/2), -127, 127)  as int8
    nnz   = number of non-zeros in the tile                (int32)
so a single pass over HBM produces both the int8 payload for the backward
matmuls and the tile-occupancy map the block-sparse matmul kernel uses for
tile skipping.

The nnz map is written one row of tiles at a time: the output block for
grid row ``i`` is the whole ``(1, N // bn)`` row of counts, resident in
VMEM across the inner ``j`` loop, and step ``j`` selects its count into
lane ``j``. Mosaic refuses a ``(1, 1)`` block of a larger array (a block's
last two dims must be (8, 128)-divisible or span the array); a full-width
row block spans the array's last two dims.

Delta (= s * std, a per-tensor scalar) and the dither noise are computed
outside (std is a global reduction; noise comes from the framework RNG so
the kernel stays deterministic given its inputs).

Tiles are (8m, 128)-aligned: the VPU lane width is 128 and sublane 8, so
bm in {8,16,32,...}, bn multiple of 128.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.backend import default_interpret, write_tile_count


def _nsd_kernel(x_ref, noise_ref, delta_ref, k_ref, nnz_ref):
    j = pl.program_id(1)
    x = x_ref[...].astype(jnp.float32)
    nu = noise_ref[...].astype(jnp.float32)
    delta = delta_ref[0, 0]
    safe = jnp.maximum(delta, jnp.finfo(jnp.float32).tiny)
    k = jnp.floor((x + nu) / safe + 0.5)
    k = jnp.clip(k, -127.0, 127.0)
    k = jnp.where(delta > 0.0, k, jnp.zeros_like(k)).astype(jnp.int32)
    k_ref[...] = k.astype(jnp.int8)
    write_tile_count(nnz_ref, j, jnp.sum((k != 0).astype(jnp.int32)))


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def nsd_quantize_blocked(x: jax.Array, noise: jax.Array, delta: jax.Array,
                         *, bm: int = 128, bn: int = 512,
                         interpret: Optional[bool] = None):
    """x, noise: (M, N) with M % bm == 0, N % bn == 0; delta: scalar f32.

    Returns (k int8 (M, N), nnz int32 (M//bm, N//bn)).
    """
    interpret = default_interpret(interpret)
    M, N = x.shape
    assert M % bm == 0 and N % bn == 0, (x.shape, bm, bn)
    grid = (M // bm, N // bn)
    delta2d = jnp.reshape(delta.astype(jnp.float32), (1, 1))
    k, nnz_rows = pl.pallas_call(
        _nsd_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((None, 1, N // bn), lambda i, j: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((M, N), jnp.int8),
            jax.ShapeDtypeStruct((M // bm, 1, N // bn), jnp.int32),
        ],
        interpret=interpret,
    )(x, noise, delta2d)
    return k, nnz_rows[:, 0, :]
