"""The Pallas levels compact/expand kernels (repro.kernels.levels): the
chunk-local butterfly routing is BIT-EXACT against the cumsum oracle and
against the wire format's global `_compact`/`_expand`, interpret mode on
any host; tests/test_tpu_compile.py compiles them for a described chip."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.levels.levels import (levels_compact_blocked,
                                         levels_expand_blocked)
from repro.kernels.levels.ref import compact_columns_ref, expand_columns_ref
from repro.quant import wire

CHUNK = 256

# compiled for a described chip in tests/test_tpu_compile.py
INTERPRET_MODES = [pytest.param(True, id="interpret")]


@pytest.fixture(params=INTERPRET_MODES)
def interpret(request):
    return request.param


def _sparse_cols(key, cols, density=0.3):
    k = jax.random.fold_in(key, 17)
    vals = jax.random.randint(k, (CHUNK, cols), -127, 128, jnp.int32)
    keep = jax.random.uniform(jax.random.fold_in(k, 1),
                              (CHUNK, cols)) < density
    return jnp.where(keep, vals, 0).astype(jnp.int8)


@pytest.mark.parametrize("cols", [1, 3, 128, 200])
def test_compact_vs_ref(key, cols, interpret):
    kt = _sparse_cols(key, cols)
    lv, cnt = levels_compact_blocked(kt, interpret=interpret)
    lv_ref, cnt_ref = compact_columns_ref(kt)
    np.testing.assert_array_equal(np.asarray(lv), np.asarray(lv_ref))
    np.testing.assert_array_equal(np.asarray(cnt), np.asarray(cnt_ref))


@pytest.mark.parametrize("cols", [1, 3, 128, 200])
def test_expand_inverts_compact(key, cols, interpret):
    kt = _sparse_cols(key, cols)
    lv, _ = levels_compact_blocked(kt, interpret=interpret)
    mask = (kt != 0).astype(jnp.int8)
    back = levels_expand_blocked(lv, mask, interpret=interpret)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(kt))
    np.testing.assert_array_equal(
        np.asarray(expand_columns_ref(lv, mask)), np.asarray(kt))


@pytest.mark.parametrize("density", [0.0, 1.0])
def test_degenerate_densities(key, density, interpret):
    """All-zero columns (empty routing) and fully-dense columns (identity
    permutation) both round-trip."""
    kt = _sparse_cols(key, 8, density=density)
    lv, cnt = levels_compact_blocked(kt, interpret=interpret)
    lv_ref, cnt_ref = compact_columns_ref(kt)
    np.testing.assert_array_equal(np.asarray(lv), np.asarray(lv_ref))
    np.testing.assert_array_equal(np.asarray(cnt), np.asarray(cnt_ref))
    back = levels_expand_blocked(lv, (kt != 0).astype(jnp.int8),
                                 interpret=interpret)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(kt))


class TestWireBackend:
    """The kernels as the wire format's backend="pallas" (interpret mode):
    identical packed bytes to the jnp backend, including odd sizes that
    exercise the chunk padding."""

    @pytest.mark.parametrize("n", [CHUNK, 3 * CHUNK, 1000, 7])
    def test_compact_assembly_bit_exact(self, key, n):
        k = jax.random.randint(key, (n,), -127, 128, jnp.int32)
        keep = jax.random.uniform(jax.random.fold_in(key, 1), (n,)) < 0.25
        k_flat = jnp.where(keep, k, 0).astype(jnp.int8)
        pad = (-n) % CHUNK
        k_pad = jnp.pad(k_flat, (0, pad))
        lv_ref, nnz_ref = wire._compact(k_pad)
        lv, nnz = wire._compact_pallas(k_pad, CHUNK)
        np.testing.assert_array_equal(np.asarray(lv), np.asarray(lv_ref))
        assert int(nnz) == int(nnz_ref)
        mask = k_pad != 0
        np.testing.assert_array_equal(
            np.asarray(wire._expand_pallas(lv, mask, CHUNK)),
            np.asarray(wire._expand(lv_ref, mask)))

    def test_pack_unpack_nsd_pallas_backend(self, key):
        """End to end through the public wire API: pallas backend decodes
        to the same tensor as the jnp backend, bit for bit."""
        x = jax.random.normal(key, (7, 93), jnp.float32)
        delta = jnp.float32(0.25)
        k = jnp.round(x / delta).clip(-127, 127).astype(jnp.int32)
        p_jnp = wire.pack_indices(k, delta, x.shape, x.dtype)
        p_pl = wire.pack_indices(k, delta, x.shape, x.dtype,
                                 backend="pallas")
        np.testing.assert_array_equal(np.asarray(p_pl.levels),
                                      np.asarray(p_jnp.levels))
        np.testing.assert_array_equal(np.asarray(p_pl.bitmap),
                                      np.asarray(p_jnp.bitmap))
        np.testing.assert_array_equal(
            np.asarray(wire.unpack_nsd(p_pl, backend="pallas")),
            np.asarray(wire.unpack_nsd(p_jnp)))
