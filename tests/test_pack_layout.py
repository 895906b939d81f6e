"""Lowering-pattern guard for the bitmap pack/unpack kernels.

Mosaic (the TPU Pallas backend) cannot lower a reshape that regroups the
minor (lane) dimension — exactly the ``(bm, bn) -> (bm, bn/8, 8)`` byte
gather the original interpret-only kernels used. The rewrite routes the
byte grouping through the sublane dimension (rotate + OR-reduce), so the
invariant to protect is: *no reshape inside either kernel body changes
the trailing dimension*. This test walks the traced kernel jaxprs and
asserts that, turning the "does it compile on TPU" question into a
CPU-checkable structural property. Bit-exactness vs the wire format is
covered by tests/test_comm.py::TestPackKernels; tests/test_tpu_compile.py
compiles the kernels for a described TPU chip.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels.pack.pack import bitmap_pack_blocked, bitmap_unpack_blocked

LANE_CHANGERS = ("reshape",)


def _iter_jaxprs(jaxpr):
    yield jaxpr
    for eqn in jaxpr.eqns:
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", v)
            if hasattr(inner, "eqns"):
                yield from _iter_jaxprs(inner)


def _kernel_jaxprs(closed):
    """The pallas kernel bodies inside a traced computation."""
    for j in _iter_jaxprs(closed.jaxpr):
        for eqn in j.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn.params["jaxpr"]


def _assert_no_lane_reshape(kernel_jaxpr):
    for j in _iter_jaxprs(kernel_jaxpr):
        for eqn in j.eqns:
            if eqn.primitive.name not in LANE_CHANGERS:
                continue
            in_shape = eqn.invars[0].aval.shape
            out_shape = eqn.outvars[0].aval.shape
            assert in_shape[-1] == out_shape[-1], (
                f"lane-dim reshape {in_shape} -> {out_shape} — Mosaic "
                f"cannot lower this; keep byte grouping on the sublane dim")


@pytest.mark.parametrize("trace", [
    lambda k8: bitmap_pack_blocked(k8, interpret=True),
    lambda k8: bitmap_unpack_blocked(
        jnp.zeros((k8.shape[0], k8.shape[1] // 8), jnp.uint8),
        interpret=True),
], ids=["pack", "unpack"])
def test_kernel_has_no_lane_dim_reshape(trace):
    k8 = jnp.zeros((256, 256), jnp.int8)
    closed = jax.make_jaxpr(trace)(k8)
    kernels = list(_kernel_jaxprs(closed))
    assert kernels, "expected a pallas_call in the traced computation"
    for kj in kernels:
        _assert_no_lane_reshape(kj)


def test_guard_would_catch_the_old_layout():
    """Self-check: the assertion actually fires on a lane-dim regroup."""
    def old_style(x):
        bm, bn = x.shape
        return jnp.sum(x.reshape(bm, bn // 8, 8), axis=-1)

    closed = jax.make_jaxpr(old_style)(jnp.zeros((128, 128), jnp.int8))
    with pytest.raises(AssertionError, match="lane-dim reshape"):
        _assert_no_lane_reshape(closed.jaxpr)


def test_pack_uses_sublane_rotates():
    """The OR-reduce tree is built from TPU-native rolls, not gathers."""
    k8 = jnp.zeros((128, 128), jnp.int8)
    closed = jax.make_jaxpr(lambda k: bitmap_pack_blocked(k, interpret=True))(
        k8)
    prims = {e.primitive.name
             for kj in _kernel_jaxprs(closed)
             for j in _iter_jaxprs(kj)
             for e in j.eqns}
    assert "tpu_roll" in prims or "roll" in prims, sorted(prims)
