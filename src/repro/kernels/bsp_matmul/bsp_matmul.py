"""Block-sparse quantized matmul kernels (Pallas, TPU target).

TPU adaptation of the paper's sparse backward products (DESIGN.md §4):
element-granular sparsity cannot skip MACs on a 128x128 systolic MXU, so we
skip at *tile* granularity. The NSD kernel emits a (M/128, K/128) tile-
occupancy map; a masked-off tile contributes nothing to the product, even
when its data is non-zero.

HBM->VMEM traffic is skipped through the *fetch map*: alongside the mask,
the wrappers prefetch ``fetch[i, k] = index of the last occupied K-block at
or before k in row i`` (clamped to 0 when none). The A/B block index maps
return ``fetch[i, k]`` instead of ``k``, so every skipped grid step re-names
the block it already holds — Pallas only issues a copy when the block index
*changes*, which means a skipped block costs neither MXU cycles nor operand
DMA for A or B. The worst case is one redundant fetch per row when a row's
leading blocks are all masked (fetch clamps to 0).

Two variants:
  * ``bsp_matmul``      — A is (int8 k, Delta) NSD output, B stays bf16/f32;
                          A is dequantized in VMEM before the dot. Its
                          compute block is the 128 x 128 mask tile.
  * ``bsp_matmul_int8`` — both operands int8, int32 MXU accumulation,
                          rescale on exit: the paper's "8bit + dithered"
                          column mapped onto the 2x-throughput int8 MXU path.
                          Its compute blocks are sized from the operand
                          shapes (``_int8_blocks``), up to 1024 a side: a
                          128^3 grid step is bound by pipeline overhead, not
                          the MXU. Skipping then holds per block (a block with
                          no live tile is skipped whole); the mask's
                          semantics still hold per tile (the dead tiles of a
                          partly live block are zeroed in VMEM before the dot).

The masks and fetch map ride in scalar-prefetch SMEM
(PrefetchScalarGridSpec) so they are available to the grid index maps
*before* blocks are fetched. ``interpret=None`` resolves backend-aware
(interpret off-TPU, compiled on TPU — ``repro.kernels.backend``).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import default_interpret


def _bsp_kernel_dequant(mask_ref, fetch_ref, a_ref, b_ref, delta_ref, o_ref,
                        acc_ref):
    i, j, k = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(mask_ref[i, k] != 0)
    def _accum():
        a = a_ref[...].astype(jnp.float32)
        b = b_ref[...].astype(jnp.float32)
        acc_ref[...] += jnp.dot(a, b, preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _finish():
        o_ref[...] = (acc_ref[...] * delta_ref[0, 0]).astype(o_ref.dtype)


def fetch_map(mask: jax.Array) -> jax.Array:
    """``fetch[i, k]`` = last occupied K-block index <= k in row i (else 0).

    When ``mask[i, k] == 0`` the fetch index equals the previous step's, so
    the block index maps below re-name the resident block and Pallas skips
    the HBM->VMEM copy entirely.
    """
    kt = mask.shape[1]
    idx = jnp.where(mask != 0, jnp.arange(kt, dtype=jnp.int32)[None, :], -1)
    return jnp.maximum(jax.lax.cummax(idx, axis=1), 0).astype(jnp.int32)


def _grid_spec(M, K, N, bm, bk, bn, acc_dtype):
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(M // bm, N // bn, K // bk),
        in_specs=[
            # masked steps return fetch[i, k] == the previous occupied
            # index: same block index -> no new operand DMA
            pl.BlockSpec((bm, bk), lambda i, j, k, mask, fetch: (i, fetch[i, k])),
            pl.BlockSpec((bk, bn), lambda i, j, k, mask, fetch: (fetch[i, k], j)),
            pl.BlockSpec((1, 1), lambda i, j, k, mask, fetch: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k, mask, fetch: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), acc_dtype)],
    )


@functools.partial(jax.jit,
                   static_argnames=("bm", "bk", "bn", "out_dtype",
                                    "interpret"))
def bsp_matmul(k_q: jax.Array, delta: jax.Array, b: jax.Array,
               mask: jax.Array, *, bm: int = 128, bk: int = 128,
               bn: int = 128, out_dtype=jnp.float32,
               interpret: Optional[bool] = None) -> jax.Array:
    """(dequant(k_q) @ b) with tile skipping (compute AND operand fetch).

    k_q: (M, K) int8 NSD indices; delta: scalar; b: (K, N) f32/bf16;
    mask: (M//bm, K//bk) int32 tile-occupancy (0 = all-zero tile).
    """
    interpret = default_interpret(interpret)
    M, K = k_q.shape
    K2, N = b.shape
    assert K == K2 and M % bm == 0 and K % bk == 0 and N % bn == 0
    delta2d = jnp.reshape(delta.astype(jnp.float32), (1, 1))
    mask = mask.astype(jnp.int32)
    return pl.pallas_call(
        _bsp_kernel_dequant,
        grid_spec=_grid_spec(M, K, N, bm, bk, bn, jnp.float32),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        interpret=interpret,
    )(mask, fetch_map(mask), k_q, b, delta2d)


# ---------------------------------------------------------------------------
# int8 variant: the mask tile is not the compute block
# ---------------------------------------------------------------------------

# Largest compute block per dimension (bm, bk, bn) and the VMEM a block set
# may take. Chosen by a v5e sweep (benchmarks/int8_block_sweep.py): a 128^3
# grid step costs ~0.3 us of pipeline overhead against 11 ns of MXU work;
# 1024 a side takes 1.17x XLA's int8 dot at mamba2-370m's in-projection and
# matches it at the out-projection, and 2048 on any side gains at most 6%.
_INT8_BLOCK_CAPS = (1024, 1024, 1024)
_INT8_VMEM_BUDGET = 32 * 2**20

# (M, K, N) -> (bm, bk, bn) of every int8 call traced in this process,
# recorded at trace time (the pattern of ops.KERNEL_FALLBACKS).
INT8_BLOCKS: dict = {}

_DEAD, _FULL, _PARTIAL = 0, 1, 2


def _int8_vmem_bytes(bm: int, bk: int, bn: int, out_bytes: int) -> int:
    return (2 * (bm * bk + bk * bn)      # int8 A and B blocks, double-buffered
            + bm * bk                    # A copy with its dead tiles zeroed
            + 2 * 4 * bm * bn            # int32 accumulator and one dot result
            + 2 * out_bytes * bm * bn)   # output block, double-buffered


def _int8_blocks(M: int, K: int, N: int, tile: int = 128,
                 out_bytes: int = 4, caps=_INT8_BLOCK_CAPS,
                 budget: int = _INT8_VMEM_BUDGET):
    """Compute blocks (bm, bk, bn) for an (M, K) @ (K, N) int8 call.

    Each is the largest multiple of ``tile`` that divides its dimension and
    stays under its cap; while the set exceeds the VMEM budget, the largest
    block steps down to its next divisor. Depends on the shapes alone.
    """
    assert M % tile == 0 and K % tile == 0 and N % tile == 0
    options = [[b for b in range(tile * (min(cap, d) // tile), 0, -tile)
                if d % b == 0] for d, cap in zip((M, K, N), caps)]
    pick = [0, 0, 0]
    while True:
        blocks = tuple(o[p] for o, p in zip(options, pick))
        if _int8_vmem_bytes(*blocks, out_bytes) <= budget:
            return blocks
        shrinkable = [d for d in range(3) if pick[d] + 1 < len(options[d])]
        assert shrinkable, (M, K, N, blocks)
        pick[max(shrinkable, key=lambda d: blocks[d])] += 1


def _block_state(mask: jax.Array, tm: int, tk: int) -> jax.Array:
    """Per compute block: _DEAD (no live tile), _FULL or _PARTIAL."""
    mt, kt = mask.shape
    t = (mask != 0).reshape(mt // tm, tm, kt // tk, tk)
    return jnp.where(t.all(axis=(1, 3)), _FULL,
                     jnp.where(t.any(axis=(1, 3)), _PARTIAL, _DEAD)
                     ).astype(jnp.int32)


def _bsp_kernel_int8(mask_ref, state_ref, fetch_ref, a_ref, b_ref, scale_ref,
                     o_ref, acc_ref, a_live_ref, *, tile):
    i, k = pl.program_id(0), pl.program_id(2)
    bm, bk = a_ref.shape
    tm, tk = bm // tile, bk // tile

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def accum(a):
        # int8 x int8 -> int32: the MXU-native 2x-throughput path on v5e
        acc_ref[...] += jax.lax.dot_general(
            a, b_ref[...], dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)

    @pl.when(state_ref[i, k] == _FULL)
    def _full():
        accum(a_ref[...])

    @pl.when(state_ref[i, k] == _PARTIAL)
    def _partial():
        # a masked tile contributes nothing even when its data is non-zero
        a_live_ref[...] = a_ref[...]
        for r in range(tm):
            for c in range(tk):
                @pl.when(mask_ref[i * tm + r, k * tk + c] == 0)
                def _zero(r=r, c=c):
                    a_live_ref[r * tile:(r + 1) * tile,
                               c * tile:(c + 1) * tile] = jnp.zeros(
                                   (tile, tile), jnp.int8)
        accum(a_live_ref[...])

    @pl.when(k == pl.num_programs(2) - 1)
    def _finish():
        o_ref[...] = (acc_ref[...].astype(jnp.float32)
                      * scale_ref[0, 0]).astype(o_ref.dtype)


def _int8_call(k_q, b_q, scale, mask, *, tile, blocks, out_dtype, interpret):
    """The int8 pallas_call at explicit compute blocks."""
    M, K = k_q.shape
    N = b_q.shape[1]
    bm, bk, bn = blocks
    mask = mask.astype(jnp.int32)
    state = _block_state(mask, bm // tile, bk // tile)
    # Mosaic's scoped VMEM: the blocks' buffers and 4 MiB for its own, never
    # under v5e's default of 16 MiB
    limit = max(16 * 2**20, 4 * 2**20 + _int8_vmem_bytes(
        *blocks, jnp.dtype(out_dtype).itemsize))
    # index maps get (i, j, k, mask, state, fetch); a dead block re-names
    # the resident one, so it costs neither DMA nor MXU
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(M // bm, N // bn, K // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k, m, s, f: (i, f[i, k])),
            pl.BlockSpec((bk, bn), lambda i, j, k, m, s, f: (f[i, k], j)),
            pl.BlockSpec((1, 1), lambda i, j, k, m, s, f: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k, m, s, f: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32),
                        pltpu.VMEM((bm, bk), jnp.int8)],
    )
    return pl.pallas_call(
        functools.partial(_bsp_kernel_int8, tile=tile),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=limit),
        interpret=interpret,
    )(mask, state, fetch_map(state), k_q, b_q,
      jnp.reshape(scale.astype(jnp.float32), (1, 1)))


@functools.partial(jax.jit, static_argnames=("tile", "out_dtype",
                                             "interpret"))
def bsp_matmul_int8(k_q: jax.Array, b_q: jax.Array, scale: jax.Array,
                    mask: jax.Array, *, tile: int = 128,
                    out_dtype=jnp.float32,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Full int8 MXU path: (k_q @ b_q) * scale with block skipping.

    k_q: (M, K) int8; b_q: (K, N) int8; scale = delta_A * scale_B (the
    per-tensor product of the two quant scales); mask: (M//tile, K//tile)
    tile-occupancy. A masked tile contributes nothing. The compute blocks
    are chosen from the shapes (``_int8_blocks``) and recorded in
    ``INT8_BLOCKS``; a block none of whose tiles is live is skipped whole.
    """
    interpret = default_interpret(interpret)
    M, K = k_q.shape
    K2, N = b_q.shape
    assert K == K2 and mask.shape == (M // tile, K // tile)
    blocks = _int8_blocks(M, K, N, tile, jnp.dtype(out_dtype).itemsize)
    INT8_BLOCKS[(M, K, N)] = blocks
    return _int8_call(k_q, b_q, scale, mask, tile=tile, blocks=blocks,
                      out_dtype=out_dtype, interpret=interpret)
