"""Backend-aware defaults shared by every Pallas kernel wrapper.

The kernels run in two modes: ``interpret=True`` executes the kernel body
with jnp ops on the host backend (bit-exact validation anywhere), while
``interpret=False`` lowers through Mosaic and requires a real TPU. The
public wrappers take ``interpret=None`` and resolve it here — interpret
off-TPU, compiled on a TPU host — so a training run on hardware gets the
compiled kernels without every caller remembering to override, and the
CPU CI keeps exercising the interpret path. tests/test_tpu_compile.py
compiles every kernel for a described chip; ``chip_smoke.py`` checks on
a real one that the training step holds each kernel as a Mosaic call.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def on_tpu() -> bool:
    """True when the default JAX backend is a TPU."""
    return jax.default_backend() == "tpu"


def default_interpret(interpret: Optional[bool]) -> bool:
    """Resolve an ``interpret=None`` kernel argument backend-aware.

    ``None`` -> interpret off-TPU, compiled on TPU; an explicit bool is
    passed through untouched.
    """
    if interpret is None:
        return not on_tpu()
    return interpret


def write_tile_count(row_ref, lane, count) -> None:
    """Store ``count`` into lane ``lane`` of a resident (1, T) count row.

    The row block stays in VMEM while the grid walks its tiles; the first
    visit zeroes it so no lane is ever read back uninitialised.
    """
    @pl.when(lane == 0)
    def _init():
        row_ref[...] = jnp.zeros_like(row_ref)

    lanes = jax.lax.broadcasted_iota(jnp.int32, row_ref.shape, 1)
    row_ref[...] = jnp.where(lanes == lane, count, row_ref[...])
