"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp ref oracles,
swept over shapes and dtypes, plus equivalence with the core (non-kernel)
dithered backward.

The direct kernel tests run in interpret mode, which is what a CPU host
can execute; tests/test_tpu_compile.py compiles the same kernels for a
described TPU chip, and ``chip_smoke.py`` runs them on one.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm import wireformat
from repro.core import (DitherCtx, DitherPolicy, Piecewise, PolicyProgram,
                        conv2d, dense, dithered_einsum, nsd)
from repro.obs import metrics as statslib
from repro.kernels import ops as kernelops
from repro.kernels.bsp_matmul.bsp_matmul import (bsp_matmul, bsp_matmul_int8,
                                                 fetch_map)
from repro.kernels.bsp_matmul.ref import (bsp_matmul_blocked_ref,
                                          bsp_matmul_int8_ref,
                                          bsp_matmul_ref)
from repro.kernels.nsd_quant.nsd_quant import nsd_quantize_blocked
from repro.kernels.nsd_quant.ref import nsd_quantize_blocked_ref
from repro.kernels.ops import dithered_backward_matmuls, nsd_quantize_kernel


SHAPES = [(128, 128), (256, 512), (384, 128)]
DTYPES = [jnp.float32, jnp.bfloat16]

INTERPRET_MODES = [pytest.param(True, id="interpret")]


@pytest.fixture(params=INTERPRET_MODES)
def interpret(request):
    return request.param


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_nsd_kernel_vs_ref(key, shape, dtype, interpret):
    x = (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)
    delta = nsd.compute_delta(x, 2.0)
    noise = nsd.dither_noise(key, shape, delta)
    bm, bn = 128, 128
    k_k, nnz_k = nsd_quantize_blocked(x, noise, delta, bm=bm, bn=bn,
                                      interpret=interpret)
    k_r, nnz_r = nsd_quantize_blocked_ref(x, noise, delta, bm=bm, bn=bn)
    np.testing.assert_array_equal(np.asarray(k_k), np.asarray(k_r))
    np.testing.assert_array_equal(np.asarray(nnz_k), np.asarray(nnz_r))


def test_nsd_kernel_vs_core(key):
    """Same RNG key => kernel output bit-identical to repro.core.nsd."""
    g = jax.random.normal(key, (256, 256), jnp.float32) * 0.01
    k_q, delta, _ = nsd_quantize_kernel(g, key, 2.0, bm=128, bn=128)
    k_core = nsd.nsd_indices(g, key, nsd.compute_delta(g, 2.0))
    np.testing.assert_array_equal(np.asarray(k_q, dtype=np.int32),
                                  np.asarray(k_core))


def test_nsd_kernel_zero_delta(key):
    x = jnp.zeros((128, 128))
    k, nnz = nsd_quantize_blocked(x, jnp.zeros_like(x), jnp.zeros(()),
                                  bm=128, bn=128)
    assert int(jnp.sum(jnp.abs(k.astype(jnp.int32)))) == 0
    assert int(jnp.sum(nnz)) == 0


@pytest.mark.parametrize("mkn", [(128, 128, 128), (256, 384, 128),
                                 (128, 256, 256)])
@pytest.mark.parametrize("dtype", DTYPES)
def test_bsp_matmul_vs_ref(key, mkn, dtype, interpret):
    M, K, N = mkn
    k_q = jax.random.randint(key, (M, K), -4, 5, jnp.int32).astype(jnp.int8)
    delta = jnp.float32(0.033)
    b = jax.random.normal(jax.random.fold_in(key, 1), (K, N), jnp.float32)
    b = b.astype(dtype)
    mask = jax.random.bernoulli(
        jax.random.fold_in(key, 2), 0.6, (M // 128, K // 128)
    ).astype(jnp.int32)
    out_k = bsp_matmul(k_q, delta, b, mask, interpret=interpret)
    out_r = bsp_matmul_ref(k_q, delta, b, mask)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=2e-2, atol=1e-2)


def test_bsp_matmul_skips_tiles(key, interpret):
    """A masked-off tile contributes nothing even if its data is nonzero."""
    M = K = N = 256
    k_q = jnp.ones((M, K), jnp.int8)
    b = jnp.ones((K, N), jnp.float32)
    mask = jnp.asarray([[1, 0], [0, 0]], jnp.int32)
    out = bsp_matmul(k_q, jnp.float32(1.0), b, mask, interpret=interpret)
    # row block 0: only first K-tile active -> 128; row block 1: all skipped
    np.testing.assert_allclose(np.asarray(out[:128]), 128.0)
    np.testing.assert_allclose(np.asarray(out[128:]), 0.0)


@pytest.mark.parametrize("mkn", [(128, 128, 128), (256, 128, 384)])
def test_bsp_matmul_int8_vs_ref(key, mkn, interpret):
    M, K, N = mkn
    k_q = jax.random.randint(key, (M, K), -8, 9, jnp.int32).astype(jnp.int8)
    b_q = jax.random.randint(jax.random.fold_in(key, 1), (K, N), -127, 128,
                             jnp.int32).astype(jnp.int8)
    scale = jnp.float32(1.7e-3)
    mask = jnp.ones((M // 128, K // 128), jnp.int32)
    out_k = bsp_matmul_int8(k_q, b_q, scale, mask, interpret=interpret)
    out_r = bsp_matmul_int8_ref(k_q, b_q, scale, mask)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=1e-5)


class TestFullBackward:
    def test_matches_core_dithered_semantics(self, key):
        T, K, N = 256, 128, 256
        g = jax.random.normal(key, (T, N), jnp.float32) * 0.01
        x = jax.random.normal(jax.random.fold_in(key, 1), (T, K))
        w = jax.random.normal(jax.random.fold_in(key, 2), (K, N)) * 0.1
        dx, dw = dithered_backward_matmuls(g, x, w, key, 2.0,
                                           int8_operands=False)
        gq = nsd.nsd_quantize(g, key, 2.0)
        np.testing.assert_allclose(np.asarray(dx), np.asarray(gq @ w.T),
                                   rtol=1e-3, atol=1e-4)
        np.testing.assert_allclose(np.asarray(dw), np.asarray(x.T @ gq),
                                   rtol=1e-3, atol=1e-4)

    def test_int8_operand_path_error_small(self, key):
        T, K, N = 256, 128, 256
        g = jax.random.normal(key, (T, N), jnp.float32) * 0.01
        x = jax.random.normal(jax.random.fold_in(key, 1), (T, K))
        w = jax.random.normal(jax.random.fold_in(key, 2), (K, N)) * 0.1
        dx8, dw8 = dithered_backward_matmuls(g, x, w, key, 2.0,
                                             int8_operands=True)
        gq = nsd.nsd_quantize(g, key, 2.0)
        rel_dx = float(jnp.linalg.norm(dx8 - gq @ w.T)
                       / (jnp.linalg.norm(gq @ w.T) + 1e-12))
        rel_dw = float(jnp.linalg.norm(dw8 - x.T @ gq)
                       / (jnp.linalg.norm(x.T @ gq) + 1e-12))
        assert rel_dx < 0.03 and rel_dw < 0.03, (rel_dx, rel_dw)

    def test_high_sparsity_skips_most_tiles(self, key):
        g = jax.random.normal(key, (512, 512), jnp.float32) * 0.01
        # NOTE: the dither key must be independent of the data key, else the
        # noise correlates with the signal and sparsity drops (a real
        # pitfall this test documents)
        qkey = jax.random.fold_in(key, 1234)
        k_q, delta, nnz = nsd_quantize_kernel(g, qkey, 16.0, bm=128, bn=128)
        sparsity = float(jnp.mean(k_q == 0))
        assert sparsity > 0.93, sparsity


# ---------------------------------------------------------------------------
# fetch map: the index-map trick that suppresses operand DMA on masked tiles
# ---------------------------------------------------------------------------

class TestFetchMap:
    def test_values(self):
        mask = jnp.asarray([[0, 1, 0, 0, 1],
                            [0, 0, 0, 0, 0],
                            [1, 0, 1, 0, 0]], jnp.int32)
        f = np.asarray(fetch_map(mask))
        # masked step re-names the last occupied tile at-or-before it;
        # leading masked tiles (and all-zero rows) clamp to 0
        np.testing.assert_array_equal(f, [[0, 1, 1, 1, 4],
                                          [0, 0, 0, 0, 0],
                                          [0, 0, 2, 2, 2]])

    def test_full_mask_is_identity(self):
        mask = jnp.ones((3, 7), jnp.int32)
        np.testing.assert_array_equal(
            np.asarray(fetch_map(mask)),
            np.broadcast_to(np.arange(7, dtype=np.int32), (3, 7)))

    def test_masked_steps_never_change_block_index(self):
        mask = (jax.random.bernoulli(jax.random.PRNGKey(5), 0.4, (6, 9))
                .astype(jnp.int32))
        f = np.asarray(fetch_map(mask))
        m = np.asarray(mask)
        # occupied step fetches itself; masked step repeats the previous
        # fetch index (so Pallas skips the HBM->VMEM copy)
        for i in range(6):
            for k in range(9):
                if m[i, k]:
                    assert f[i, k] == k
                elif k > 0:
                    assert f[i, k] == f[i, k - 1]
                else:
                    assert f[i, k] == 0


# ---------------------------------------------------------------------------
# occupancy: one representation — fused-kernel nnz == bitmap mask == dense
# ---------------------------------------------------------------------------

def _dense_tile_mask(k, bm=128, bk=128):
    """Dense oracle: tile mask recomputed from the int8 tensor itself."""
    occ = (np.asarray(k) != 0).astype(np.int64)
    M, K = occ.shape
    occ = np.pad(occ, ((0, (-M) % bm), (0, (-K) % bk)))
    t = occ.reshape(occ.shape[0] // bm, bm, occ.shape[1] // bk, bk).sum((1, 3))
    return (t > 0).astype(np.int32)


class TestOccupancySingleSource:
    def test_fused_nnz_matches_dense_recompute(self, key):
        """Satellite pin: the nnz map the fused kernel emits equals the
        dense ``reshape(...).sum((1, 3))`` recompute bit-exactly — so the
        pipeline keeping the kernel's map (instead of discarding it, the
        pre-fix behavior) changes nothing but the extra pass."""
        g = jax.random.normal(key, (200, 300), jnp.float32) * 0.01
        q = kernelops.quantize_and_mask(g, key, 2.0)
        occ = (np.asarray(q.k) != 0).astype(np.int64)
        Mp, Np = occ.shape
        dense_nnz = occ.reshape(Mp // 128, 128, Np // 128, 128).sum((1, 3))
        np.testing.assert_array_equal(np.asarray(q.nnz), dense_nnz)

    def test_mask_derived_from_bitmap_matches_nnz_and_dense(self, key):
        g = jax.random.normal(key, (96, 200), jnp.float32) * 0.01
        q = kernelops.quantize_and_mask(g, key, 2.0)
        np.testing.assert_array_equal(np.asarray(q.mask),
                                      (np.asarray(q.nnz) > 0).astype(np.int32))
        np.testing.assert_array_equal(np.asarray(q.mask),
                                      _dense_tile_mask(q.k))
        np.testing.assert_array_equal(
            np.asarray(wireformat.tile_nnz_from_bitmap(q.bitmap)),
            np.asarray(q.nnz))

    def test_padding_tiles_are_masked_off(self, key):
        """Zero inputs (incl. the zero padding) quantize to k == 0 — so a
        tile holding only zeros + padding reads 0 in the mask and is
        skipped. This is the property that replaced the silent
        ``_kernel_shapes_ok`` dense fallback."""
        g = jax.random.normal(key, (96, 200), jnp.float32)  # pads to 128x256
        g = g.at[:, 128:].set(0.0)  # tile col 1 = zero live cols + padding
        q = kernelops.quantize_and_mask(g, key, 0.5)  # dense-ish quantizer
        kq = np.asarray(q.k)
        assert kq[:, 200:].max() == 0 and kq[:, 200:].min() == 0
        assert int(np.asarray(q.mask)[:, -1].max()) == 0  # all-zero+pad tile
        assert int(np.asarray(q.mask)[:, 0].max()) == 1   # live tile kept

    def test_kernel_nnz_matches_ref_nnz_after_pipeline(self, key):
        g = jax.random.normal(key, (256, 256), jnp.float32) * 0.01
        k_q, delta, nnz = nsd_quantize_kernel(g, key, 2.0, bm=128, bn=128)
        occ = (np.asarray(k_q) != 0).astype(np.int64)
        np.testing.assert_array_equal(
            np.asarray(nnz), occ.reshape(2, 128, 2, 128).sum((1, 3)))


ADVERSARIAL_SHAPES = [
    (128, 128),   # exactly one tile
    (1, 8),       # single sub-tile row, byte-aligned
    (96, 200),    # non-multiple of the tile in both dims
    (130, 72),    # crosses a tile boundary by 2 rows
    (257, 384),   # one row over two tiles
    (37, 129),    # K % 8 != 0: bitmap bytes straddle rows
]


class TestBitmapTileMaskProperties:
    """Packed-bitmap tile mask == dense-recomputed mask, adversarially."""

    @pytest.mark.parametrize("shape", ADVERSARIAL_SHAPES)
    @pytest.mark.parametrize("fill", ["random", "zero", "dense"])
    def test_from_packed_matches_dense(self, key, shape, fill):
        if fill == "zero":
            k = jnp.zeros(shape, jnp.int8)
        elif fill == "dense":
            k = jnp.ones(shape, jnp.int8)
        else:
            k = jnp.where(
                jax.random.bernoulli(key, 0.05, shape),
                jax.random.randint(jax.random.fold_in(key, 1), shape, 1, 127,
                                   jnp.int32),
                0).astype(jnp.int8)
        p = wireformat.pack_indices(k, jnp.float32(0.1), shape, jnp.float32)
        got = np.asarray(wireformat.tile_mask_from_packed(p))
        np.testing.assert_array_equal(got, _dense_tile_mask(k))

    @pytest.mark.parametrize("shape", [(128, 128), (96, 200), (130, 72),
                                       (1, 8)])
    def test_from_bitmap_matches_dense(self, key, shape):
        k = jnp.where(jax.random.bernoulli(key, 0.03, shape), 7, 0
                      ).astype(jnp.int8)
        bitmap = wireformat.pack_bitmap(
            jnp.pad(k, ((0, 0), (0, (-shape[1]) % 8))) != 0)
        got = np.asarray(wireformat.tile_mask_from_bitmap(bitmap))
        np.testing.assert_array_equal(got, _dense_tile_mask(k))

    def test_popcount(self):
        x = jnp.arange(256, dtype=jnp.uint8)
        np.testing.assert_array_equal(
            np.asarray(wireformat.popcount_u8(x)),
            np.asarray([bin(i).count("1") for i in range(256)]))


# ---------------------------------------------------------------------------
# bit-exactness: interpret-mode kernels vs order-exact oracles
# ---------------------------------------------------------------------------

class TestBitExactOracles:
    def test_f32_kernel_bit_exact_vs_blocked_ref(self, key):
        M, K, N = 256, 384, 128
        k_q = jax.random.randint(key, (M, K), -8, 9, jnp.int32
                                 ).astype(jnp.int8)
        b = jax.random.normal(jax.random.fold_in(key, 1), (K, N), jnp.float32)
        mask = jax.random.bernoulli(jax.random.fold_in(key, 2), 0.5,
                                    (M // 128, K // 128)).astype(jnp.int32)
        delta = jnp.float32(0.033)
        out = bsp_matmul(k_q, delta, b, mask, interpret=True)
        ref = bsp_matmul_blocked_ref(k_q, delta, b, mask)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_int8_kernel_bit_exact_vs_ref(self, key):
        M, K, N = 256, 256, 128
        k_q = jax.random.randint(key, (M, K), -127, 128, jnp.int32
                                 ).astype(jnp.int8)
        b_q = jax.random.randint(jax.random.fold_in(key, 1), (K, N), -127,
                                 128, jnp.int32).astype(jnp.int8)
        mask = jax.random.bernoulli(jax.random.fold_in(key, 2), 0.5,
                                    (M // 128, K // 128)).astype(jnp.int32)
        out = bsp_matmul_int8(k_q, b_q, jnp.float32(1e-3), mask,
                              interpret=True)
        ref = bsp_matmul_int8_ref(k_q, b_q, jnp.float32(1e-3), mask)
        # int32 accumulation is exact in any order -> bit-exact, not close
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


# ---------------------------------------------------------------------------
# VARIANT_KERNEL end-to-end through dense / conv2d / dithered_einsum
# ---------------------------------------------------------------------------

def _ctx(key, variant, **kw):
    return DitherCtx.for_step(key, 0, DitherPolicy(variant=variant, s=1.0,
                                                   **kw))


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-12))


class TestKernelVariantParity:
    """kernel variant vs the paper path on the SAME key: the only source
    of divergence is the int8 operand quantization of x/w (<3% rel)."""

    def test_dense_nonaligned(self, key):
        x = jax.random.normal(jax.random.fold_in(key, 1), (96, 200))
        w = jax.random.normal(jax.random.fold_in(key, 2), (200, 72)) * 0.1

        def loss(x, w, c):
            return 0.5 * jnp.sum(dense(x, w, ctx=c, name="fc") ** 2)

        gk = jax.grad(loss, argnums=(0, 1))(x, w, _ctx(key, "kernel"))
        gp = jax.grad(loss, argnums=(0, 1))(x, w, _ctx(key, "paper"))
        assert _rel(gk[0], gp[0]) < 0.03, _rel(gk[0], gp[0])
        assert _rel(gk[1], gp[1]) < 0.03, _rel(gk[1], gp[1])

    def test_conv2d_vs_paper(self, key):
        x = jax.random.normal(jax.random.fold_in(key, 1), (2, 10, 10, 7))
        w = jax.random.normal(jax.random.fold_in(key, 2), (3, 3, 7, 13)) * 0.2

        def loss(x, w, c):
            return 0.5 * jnp.sum(
                conv2d(x, w, strides=(1, 1), padding="SAME", ctx=c,
                       name="cv") ** 2)

        gk = jax.grad(loss, argnums=(0, 1))(x, w, _ctx(key, "kernel"))
        gp = jax.grad(loss, argnums=(0, 1))(x, w, _ctx(key, "paper"))
        assert _rel(gk[0], gp[0]) < 0.03, _rel(gk[0], gp[0])
        assert _rel(gk[1], gp[1]) < 0.03, _rel(gk[1], gp[1])

    def test_conv2d_strided_valid_vs_paper(self, key):
        x = jax.random.normal(jax.random.fold_in(key, 1), (2, 9, 9, 5))
        w = jax.random.normal(jax.random.fold_in(key, 2), (3, 3, 5, 8)) * 0.2

        def loss(x, w, c):
            return 0.5 * jnp.sum(
                conv2d(x, w, strides=(2, 2), padding="VALID", ctx=c,
                       name="cv2") ** 2)

        gk = jax.grad(loss, argnums=(0, 1))(x, w, _ctx(key, "kernel"))
        gp = jax.grad(loss, argnums=(0, 1))(x, w, _ctx(key, "paper"))
        assert _rel(gk[0], gp[0]) < 0.03, _rel(gk[0], gp[0])
        assert _rel(gk[1], gp[1]) < 0.03, _rel(gk[1], gp[1])

    @pytest.mark.parametrize("spec,xs,ws", [
        ("ecd,edf->ecf", (3, 17, 19), (3, 19, 11)),  # batched (expert FFN)
        ("tk,kn->tn", (33, 21), (21, 9)),            # plain 2-D
        ("btk,kn->btn", (2, 15, 21), (21, 9)),       # leading batch, 2-D w
    ])
    def test_einsum_vs_paper(self, key, spec, xs, ws):
        x = jax.random.normal(jax.random.fold_in(key, 1), xs)
        w = jax.random.normal(jax.random.fold_in(key, 2), ws) * 0.3

        def loss(x, w, c):
            return 0.5 * jnp.sum(
                dithered_einsum(spec, x, w, ctx=c, name="ex") ** 2)

        gk = jax.grad(loss, argnums=(0, 1))(x, w, _ctx(key, "kernel"))
        gp = jax.grad(loss, argnums=(0, 1))(x, w, _ctx(key, "paper"))
        assert _rel(gk[0], gp[0]) < 0.03, _rel(gk[0], gp[0])
        assert _rel(gk[1], gp[1]) < 0.03, _rel(gk[1], gp[1])

    def test_unsupported_einsum_counts_fallback_and_still_correct(self, key):
        x = jax.random.normal(jax.random.fold_in(key, 1), (5, 7, 6))
        w = jax.random.normal(jax.random.fold_in(key, 2), (5, 7, 4))
        reason = "einsum:unsupported-form:bcd,bcf->bdf"
        before = kernelops.KERNEL_FALLBACKS.get(reason, 0)

        def loss(x, w, c):
            return jnp.sum(
                dithered_einsum("bcd,bcf->bdf", x, w, ctx=c, name="fb") ** 2)

        gk = jax.grad(loss, argnums=(0, 1))(x, w, _ctx(key, "kernel"))
        assert kernelops.KERNEL_FALLBACKS.get(reason, 0) > before
        # the fallback is the generic quantized path == paper semantics
        gp = jax.grad(loss, argnums=(0, 1))(x, w, _ctx(key, "paper"))
        np.testing.assert_array_equal(np.asarray(gk[0]), np.asarray(gp[0]))
        np.testing.assert_array_equal(np.asarray(gk[1]), np.asarray(gp[1]))

    def test_grouped_conv_counts_fallback(self, key):
        x = jax.random.normal(jax.random.fold_in(key, 1), (1, 6, 6, 4))
        w = jax.random.normal(jax.random.fold_in(key, 2), (3, 3, 2, 4)) * 0.2
        reason = "conv:groups-or-lhs-dilation"
        before = kernelops.KERNEL_FALLBACKS.get(reason, 0)

        def loss(x, w, c):
            return jnp.sum(conv2d(x, w, feature_group_count=2, ctx=c,
                                  name="gcv") ** 2)

        g = jax.grad(loss, argnums=(0, 1))(x, w, _ctx(key, "kernel"))
        assert kernelops.KERNEL_FALLBACKS.get(reason, 0) > before
        assert all(bool(jnp.all(jnp.isfinite(a))) for a in g)


class TestKernelTelemetryDedup:
    def test_emitted_stats_match_core_quantizer(self, key):
        """Satellite pin: the kernel path's telemetry comes from the SAME
        k tensor the matmuls consume — bit-identical to
        ``quant_stats(nsd_indices(g2d, key, delta))`` for the same key, so
        the applied gradient and the reported sparsity can never diverge."""
        x = jax.random.normal(jax.random.fold_in(key, 1), (40, 60))
        w = jax.random.normal(jax.random.fold_in(key, 2), (60, 24)) * 0.1
        ctx = _ctx(key, "kernel", collect_stats=True, stats_tag="kd/")

        def loss(x, w):
            return 0.5 * jnp.sum(dense(x, w, ctx=ctx, name="fc") ** 2)

        statslib.reset()
        jax.grad(loss, argnums=(0, 1))(x, w)
        jax.effects_barrier()
        row = statslib.rows("kd/fc")[0]
        # reproduce the cotangent (g = y for this loss) and the layer key
        g2d = x @ w
        lkey = ctx.resolve("fc").key
        delta = nsd.compute_delta(g2d, 1.0)
        k = nsd.nsd_indices(g2d, lkey, delta)
        expect = nsd.quant_stats(k, delta)
        np.testing.assert_array_equal(
            row, np.asarray([float(expect.sparsity),
                             float(expect.max_bitwidth),
                             float(expect.delta)], np.float32))


class TestPolicyProgramClause:
    def test_dsl_rule_enables_kernel_variant_per_layer(self, key):
        """Acceptance pin: a --policy-program clause turns the kernel
        backward on for matching layers only."""
        from repro.core.schedule import parse_program

        prog = parse_program("rule fc*:variant=kernel")
        ctx = DitherCtx.for_step(key, 0, prog.base, program=prog)
        assert ctx.resolve("fc1").spec.variant == "kernel"
        assert ctx.resolve("fc_out").spec.variant == "kernel"
        # non-matching layers keep the (paper) base variant
        assert ctx.resolve("conv0").spec.variant == "paper"


class TestKernelVariantRecompile:
    def test_s_ramp_zero_recompiles_across_all_ops(self, key):
        """Acceptance pin: a scheduled s ramp with variant=kernel compiles
        the step exactly once — dense, conv2d and dithered_einsum kernel
        backwards all take s as traced data."""
        prog = PolicyProgram(
            base=DitherPolicy(variant="kernel"),
            s=Piecewise(((0, 1.0), (2, 2.0), (4, 4.0))))
        xd = jax.random.normal(key, (8, 16))
        xc = jax.random.normal(jax.random.fold_in(key, 1), (2, 6, 6, 3))
        xe = jax.random.normal(jax.random.fold_in(key, 2), (2, 7, 9))
        traces = []

        @jax.jit
        def step(w, i, k):
            traces.append(1)  # appended at trace time only
            ctx = DitherCtx.for_step(k, i, prog.base, program=prog)

            def loss(w):
                a = dense(xd, w["wd"], ctx=ctx, name="fc")
                b = conv2d(xc, w["wc"], ctx=ctx, name="cv")
                c = dithered_einsum("ecd,edf->ecf", xe, w["we"], ctx=ctx,
                                    name="ex")
                return (jnp.sum(a ** 2) + jnp.sum(b ** 2)
                        + jnp.sum(c ** 2))

            g = jax.grad(loss)(w)
            return jax.tree.map(lambda a, b: a - 0.01 * b, w, g)

        w = {"wd": jax.random.normal(key, (16, 8)) * 0.1,
             "wc": jax.random.normal(jax.random.fold_in(key, 3),
                                     (3, 3, 3, 5)) * 0.1,
             "we": jax.random.normal(jax.random.fold_in(key, 4),
                                     (2, 9, 5)) * 0.1}
        for i in range(6):
            w = step(w, jnp.int32(i), key)
        assert len(traces) == 1, f"s ramp retraced {len(traces)} times"
