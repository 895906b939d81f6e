"""repro.comm: wire format round trips, pack kernels, compressed ring
all-reduce error bounds, CommPolicy routing, error-feedback conservation."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import stat_utils

from repro.comm import (CommPolicy, RingConfig, compress_tree,
                        init_comm_state, pack_nsd, ring_allreduce_nsd,
                        topk_error_feedback, unpack_nsd, wireformat)
from repro.comm.reduce_base import pack_hop
from repro.core import nsd
from repro.obs import metrics as statslib
from repro.kernels.pack.pack import (bitmap_pack_blocked,
                                     bitmap_unpack_blocked)
from repro.kernels.pack.ref import (bitmap_pack_blocked_ref,
                                    bitmap_unpack_blocked_ref)


class TestWireFormat:
    @pytest.mark.parametrize("shape", [(1024,), (300, 17), (8, 9, 10)])
    @pytest.mark.parametrize("s", [1.0, 4.0])
    def test_roundtrip_bit_exact_vs_core(self, key, shape, s):
        """unpack(pack(x)) == nsd_quantize_int8(x).dequantize() bit-exactly
        for the same PRNG key (the acceptance criterion)."""
        x = jax.random.normal(key, shape, jnp.float32) * 0.1
        p = pack_nsd(x, key, s)
        want = nsd.nsd_quantize_int8(x, key, s).dequantize()
        np.testing.assert_array_equal(np.asarray(unpack_nsd(p)),
                                      np.asarray(want))

    def test_roundtrip_under_jit(self, key):
        x = jax.random.normal(key, (513,), jnp.float32)
        f = jax.jit(lambda x, k: unpack_nsd(pack_nsd(x, k, 2.0)))
        want = nsd.nsd_quantize_int8(x, key, 2.0).dequantize()
        np.testing.assert_array_equal(np.asarray(f(x, key)),
                                      np.asarray(want))

    def test_bf16_dtype_preserved(self, key):
        x = jax.random.normal(key, (512,), jnp.bfloat16)
        out = unpack_nsd(pack_nsd(x, key, 2.0))
        assert out.dtype == jnp.bfloat16

    def test_bitmap_helpers_inverse(self, key):
        bits = jax.random.bernoulli(key, 0.1, (16, 256))
        packed = wireformat.pack_bitmap(bits)
        assert packed.dtype == jnp.uint8 and packed.shape == (16, 32)
        np.testing.assert_array_equal(
            np.asarray(wireformat.unpack_bitmap(packed)), np.asarray(bits))

    def test_wire_bytes_at_paper_sparsity_point(self, key):
        """At ~92% sparsity the packed tensor must be <= 25% of dense f32
        (acceptance criterion; in practice it is ~5%)."""
        x = jax.random.normal(key, (512, 512), jnp.float32)
        # dither key must be independent of the data key (else noise
        # correlates with the signal and sparsity drops — see test_kernels)
        qkey = jax.random.fold_in(key, 1234)
        s = 8.0  # ~90-92% sparsity on a gaussian (paper fig. 2)
        sparsity = float(jnp.mean(nsd.nsd_quantize(x, qkey, s) == 0))
        assert sparsity > 0.88, sparsity
        p = pack_nsd(x, qkey, s)
        ratio = int(p.wire_bytes()) / p.dense_bytes()
        assert ratio <= 0.25, (sparsity, ratio)

    def test_wire_bytes_honest_worst_case(self, key):
        """A dense (never-zero) tensor must cost MORE than 1 byte/elem —
        the format cannot under-report."""
        x = jax.random.normal(key, (2048,), jnp.float32) * 100.0
        p = pack_nsd(x, key, 0.01)  # tiny s -> almost nothing becomes zero
        assert int(p.nnz) > 1900
        assert int(p.wire_bytes()) > int(p.nnz)  # levels + bitmap + deltas

    def test_zero_tensor(self, key):
        p = pack_nsd(jnp.zeros((640,)), key, 2.0)
        assert int(p.nnz) == 0
        np.testing.assert_array_equal(np.asarray(unpack_nsd(p)),
                                      np.zeros(640, np.float32))

    def test_outlier_hits_int8_clip_guard(self, key):
        """A single huge spike saturates INT8_CLIP (k would be ~181
        unclipped: Delta = s*std ~ s*|spike|/sqrt(n), so k ~ sqrt(n)/s)
        and the round trip must STILL be bit-exact vs repro.core.nsd —
        both sides clip identically."""
        x = (jax.random.normal(key, (8192,), jnp.float32) * 1e-3)
        x = x.at[0].set(1e6)
        p = pack_nsd(x, key, 0.5)
        assert int(jnp.max(jnp.abs(p.levels))) == nsd.INT8_CLIP
        want = nsd.nsd_quantize_int8(x, key, 0.5).dequantize()
        np.testing.assert_array_equal(np.asarray(unpack_nsd(p)),
                                      np.asarray(want))


# interpret mode only: CPU has no compiled pallas_call.
# tests/test_tpu_compile.py compiles these kernels for a described chip
INTERPRET_MODES = [True]


class TestPackKernels:
    @pytest.mark.parametrize("interpret", INTERPRET_MODES)
    @pytest.mark.parametrize("shape", [(128, 128), (256, 512), (384, 128)])
    def test_pack_kernel_vs_ref(self, key, shape, interpret):
        x = jax.random.normal(key, shape, jnp.float32)
        k8 = nsd.nsd_quantize_int8(x, key, 4.0).k
        bm_k, nnz_k = bitmap_pack_blocked(k8, interpret=interpret)
        bm_r, nnz_r = bitmap_pack_blocked_ref(k8)
        np.testing.assert_array_equal(np.asarray(bm_k), np.asarray(bm_r))
        np.testing.assert_array_equal(np.asarray(nnz_k), np.asarray(nnz_r))

    @pytest.mark.parametrize("interpret", INTERPRET_MODES)
    def test_unpack_kernel_vs_ref(self, key, interpret):
        x = jax.random.normal(key, (256, 256), jnp.float32)
        k8 = nsd.nsd_quantize_int8(x, key, 4.0).k
        bm, _ = bitmap_pack_blocked(k8, interpret=interpret)
        np.testing.assert_array_equal(
            np.asarray(bitmap_unpack_blocked(bm, interpret=interpret)),
            np.asarray(bitmap_unpack_blocked_ref(bm)))

    @pytest.mark.parametrize("interpret", INTERPRET_MODES)
    def test_kernel_roundtrip_recovers_occupancy(self, key, interpret):
        x = jax.random.normal(key, (128, 256), jnp.float32)
        k8 = nsd.nsd_quantize_int8(x, key, 2.0).k
        bm, _ = bitmap_pack_blocked(k8, interpret=interpret)
        mask = bitmap_unpack_blocked(bm, interpret=interpret)
        np.testing.assert_array_equal(
            np.asarray(mask), np.asarray((k8 != 0).astype(jnp.int8)))

    @pytest.mark.parametrize("interpret", INTERPRET_MODES)
    def test_kernel_matches_wireformat_bitmap(self, key, interpret):
        """Kernel and jnp wire-format reference share the bit convention."""
        x = jax.random.normal(key, (128, 128), jnp.float32)
        k8 = nsd.nsd_quantize_int8(x, key, 2.0).k
        bm_kernel, _ = bitmap_pack_blocked(k8, interpret=interpret)
        bm_wf = wireformat.pack_bitmap(k8)
        np.testing.assert_array_equal(np.asarray(bm_kernel),
                                      np.asarray(bm_wf))


class TestRing:
    def test_ring_matches_dense_mean_within_bound(self, key):
        """Compressed N=4 ring all-reduce vs dense average, within the
        documented NSD bound (acceptance criterion)."""
        n = 4
        gs = jnp.stack([
            jax.random.normal(jax.random.fold_in(key, i), (1000,))
            for i in range(n)])
        mean, tele = ring_allreduce_nsd(gs, key, RingConfig(s=1.0))
        dense = jnp.mean(gs, axis=0)
        err = float(jnp.max(jnp.abs(mean - dense)))
        stat_utils.assert_within_bound(err, tele.error_bound)

    def test_heavy_tailed_leaf_within_bound(self, key):
        """An embedding-like gradient (a few touched rows hold all the
        mass, max/std in the hundreds) would clip at +-127 Delta on the
        wire; hops raise Delta instead, so the bound still holds."""
        n, rows, width, hot = 4, 32768, 32, 1
        gs = []
        for i in range(n):
            k_rows, k_vals = jax.random.split(jax.random.fold_in(key, i))
            touched = jax.random.choice(k_rows, rows, (hot,), replace=False)
            g = jnp.zeros((rows, width)).at[touched].set(
                jax.random.normal(k_vals, (hot, width)))
            gs.append(g)
        gs = jnp.stack(gs)
        # within the ring segment that holds node 0's row, max/std > 127
        seg = gs[0].reshape(n, -1)
        ratio = jnp.max(jnp.abs(seg), 1) / jnp.maximum(jnp.std(seg, 1), 1e-30)
        assert float(jnp.max(ratio)) > 127
        mean, tele = ring_allreduce_nsd(gs, key, RingConfig(s=1.0))
        err = float(jnp.max(jnp.abs(mean - jnp.mean(gs, axis=0))))
        assert err <= float(tele.error_bound), (err, float(tele.error_bound))

    def test_hop_pack_never_clips(self, key):
        x = jnp.zeros((65536,)).at[7].set(1000.0).at[9].set(-3.0)
        p = pack_hop(x, key, 1.0, 256)
        assert int(jnp.max(jnp.abs(p.levels))) <= nsd.INT8_CLIP
        err = jnp.abs(unpack_nsd(p) - x)
        assert float(jnp.max(err)) <= float(p.deltas[0])

    def test_ring_wire_under_25pct_at_paper_sparsity(self, key):
        """At the ~92% sparsity operating point the whole exchange must be
        <= 25% of a dense f32 ring (acceptance criterion)."""
        n = 4
        gs = jnp.stack([
            jax.random.normal(jax.random.fold_in(key, i), (64, 64))
            for i in range(n)])
        s = 8.0
        sp = float(jnp.mean(nsd.nsd_quantize(gs[0], key, s) == 0))
        assert sp > 0.88, sp
        _, tele = ring_allreduce_nsd(gs, key, RingConfig(s=s))
        assert float(tele.ratio) <= 0.25, float(tele.ratio)

    def test_ring_error_shrinks_with_smaller_s(self, key):
        n = 4
        gs = jnp.stack([
            jax.random.normal(jax.random.fold_in(key, i), (512,))
            for i in range(n)])
        dense = jnp.mean(gs, axis=0)
        errs = {}
        for s in (0.25, 4.0):
            mean, _ = ring_allreduce_nsd(gs, key, RingConfig(s=s))
            errs[s] = float(jnp.max(jnp.abs(mean - dense)))
        assert errs[0.25] < errs[4.0], errs

    def test_single_node_is_exact_and_free(self, key):
        g = jax.random.normal(key, (7, 11))[None]
        mean, tele = ring_allreduce_nsd(g, key)
        np.testing.assert_array_equal(np.asarray(mean), np.asarray(g[0]))
        assert float(tele.wire_bytes) == 0.0

    def test_ring_is_deterministic(self, key):
        gs = jnp.stack([jax.random.normal(jax.random.fold_in(key, i), (256,))
                        for i in range(4)])
        m1, _ = ring_allreduce_nsd(gs, key)
        m2, _ = ring_allreduce_nsd(gs, key)
        np.testing.assert_array_equal(np.asarray(m1), np.asarray(m2))


class TestCommPolicy:
    def _grads(self, key):
        return {
            "dense_layer": {"w": jax.random.normal(key, (64, 64)) * 0.01,
                            "b": jax.random.normal(key, (64,)) * 0.01},
            "head": {"w": jax.random.normal(
                jax.random.fold_in(key, 1), (64, 32)) * 0.01},
        }

    def test_small_leaves_stay_dense(self, key):
        grads = self._grads(key)
        pol = CommPolicy(default="nsd", min_leaf_size=256)
        out, _, tele = compress_tree(grads, key, pol)
        # the 64-elem bias is below min_leaf_size -> exact passthrough
        np.testing.assert_array_equal(
            np.asarray(out["dense_layer"]["b"]),
            np.asarray(grads["dense_layer"]["b"]))
        assert int(tele["wire_bytes"]) < int(tele["dense_bytes"])

    def test_overrides_win(self, key):
        grads = self._grads(key)
        pol = CommPolicy(default="nsd", overrides=(("head", "dense"),))
        out, _, _ = compress_tree(grads, key, pol)
        np.testing.assert_array_equal(np.asarray(out["head"]["w"]),
                                      np.asarray(grads["head"]["w"]))

    def test_nsd_leaves_equal_wire_roundtrip(self, key):
        grads = self._grads(key)
        pol = CommPolicy(default="nsd", s=2.0, min_leaf_size=1)
        out, _, _ = compress_tree(grads, key, pol)
        w = grads["dense_layer"]["w"]
        stat_utils.assert_within_bound(
            jnp.max(jnp.abs(out["dense_layer"]["w"] - w)),
            nsd.compute_delta(w, 2.0))

    def test_unknown_mode_rejected(self):
        with pytest.raises(ValueError):
            CommPolicy(default="gzip")

    def test_collect_stats_routes_to_sink(self, key):
        statslib.reset()
        grads = self._grads(key)
        pol = CommPolicy(default="nsd", collect_stats=True,
                         stats_tag="testcomm/")
        compress_tree(grads, key, pol)
        jax.effects_barrier()
        summ = statslib.comm_summary()
        assert "testcomm/" in summ and summ["testcomm/"]["wire_bytes"] > 0


class TestErrorFeedback:
    def test_residual_conservation(self, key):
        """sent + residual == g + residual_in exactly, every round — the
        invariant that survives the migration out of ssgd.py."""
        g = jax.random.normal(key, (512,))
        state = None
        for _ in range(10):
            sent, new_state = topk_error_feedback(g, state, k_frac=0.05)
            carried_in = (state.residual if state is not None
                          else jnp.zeros(512))
            np.testing.assert_allclose(
                np.asarray(sent.reshape(-1) + new_state.residual),
                np.asarray(g + carried_in), rtol=1e-6, atol=1e-6)
            state = new_state

    def test_ssgd_reexport_is_same_function(self):
        from repro.comm import compression
        from repro.distributed import ssgd
        assert ssgd.topk_error_feedback is compression.topk_error_feedback
        assert ssgd.ErrorFeedbackState is compression.ErrorFeedbackState

    def test_topk_ef_through_policy_recovers_mass(self, key):
        g = {"w": jax.random.normal(key, (512,))}
        pol = CommPolicy(default="topk_ef", topk_frac=0.05, min_leaf_size=1)
        states = init_comm_state(g, pol)
        assert set(states) == {"w"}
        sent_total = jnp.zeros((512,))
        for _ in range(50):
            out, states, _ = compress_tree(g, key, pol, states)
            sent_total = sent_total + out["w"]
        rel = float(jnp.linalg.norm(sent_total / 50 - g["w"])
                    / jnp.linalg.norm(g["w"]))
        assert rel < 0.3, rel


class TestIntegration:
    def test_ssgd_step_with_comm_policy(self, key):
        from repro.configs import get_smoke_model
        from repro.core import DitherPolicy
        from repro.distributed import SSGDConfig, make_ssgd_step, shard_batch
        from repro.optim import OptConfig, init_opt_state

        model = get_smoke_model("mamba2-370m")
        params, _ = model.init(key)
        batch = {
            "tokens": jax.random.randint(key, (8, 16), 0, model.cfg.vocab),
            "labels": jax.random.randint(key, (8, 16), 0, model.cfg.vocab),
        }
        opt = OptConfig(lr=1e-3)
        dcfg = SSGDConfig(n_nodes=4, s_schedule="sqrt", s_base=1.0)
        step_fn, _ = make_ssgd_step(
            model, opt, dcfg, DitherPolicy(variant="paper"),
            comm_policy=CommPolicy(default="nsd", s=1.0))
        state = init_opt_state(params, opt)
        p2, s2, m, _ = step_fn(params, state, shard_batch(batch, 4), key)
        assert float(m["loss"]) > 0
        wire, dense = float(m["comm_wire_bytes"]), float(m["comm_dense_bytes"])
        assert 0 < wire < dense, (wire, dense)

    def test_trainer_with_comm_policy_still_learns(self, key):
        from repro.configs import get_smoke_model
        from repro.data import TokenStreamConfig, token_batch
        from repro.optim import OptConfig
        from repro.train.trainer import Trainer, TrainerConfig

        model = get_smoke_model("mamba2-370m")
        tscfg = TokenStreamConfig(vocab=model.cfg.vocab, seq_len=16, batch=8)
        trainer = Trainer(
            model, OptConfig(lr=1e-3),
            TrainerConfig(total_steps=12, log_every=4),
            comm_policy=CommPolicy(default="nsd", s=0.5))
        out = trainer.fit(iter(token_batch(tscfg, i) for i in range(200)))
        hist = out["history"]
        assert hist[-1]["loss"] < hist[0]["loss"] + 0.05, hist

    def test_trainer_ef_state_survives_checkpoint_resume(self, key, tmp_path):
        """topk_ef residuals ride in the checkpoint tree: a restored
        trainer continues from the saved error-feedback state."""
        from repro.configs import get_smoke_model
        from repro.data import TokenStreamConfig, token_batch
        from repro.optim import OptConfig
        from repro.train.trainer import Trainer, TrainerConfig

        model = get_smoke_model("mamba2-370m")
        tscfg = TokenStreamConfig(vocab=model.cfg.vocab, seq_len=16, batch=8)
        pol = CommPolicy(default="topk_ef", topk_frac=0.1, min_leaf_size=1)

        def make(total):
            return Trainer(model, OptConfig(lr=1e-3),
                           TrainerConfig(total_steps=total, log_every=0,
                                         ckpt_every=3, ckpt_dir=str(tmp_path)),
                           comm_policy=pol)

        t1 = make(3)
        t1.fit(iter(token_batch(tscfg, i) for i in range(100)))
        saved = {k: np.asarray(v.residual)
                 for k, v in t1._comm_state.items()}
        assert saved and any(np.abs(r).sum() > 0 for r in saved.values())

        t2 = make(6)
        params, opt_state, _ = t2.restore_or_init(key)
        assert int(opt_state["step"]) == 3
        for name, r in saved.items():
            np.testing.assert_array_equal(
                np.asarray(t2._comm_state[name].residual), r)

    def test_s_for_n_sqrt_is_python_float(self):
        from repro.distributed import SSGDConfig
        s = SSGDConfig(n_nodes=4, s_schedule="sqrt", s_base=2.0).s_for_n()
        assert isinstance(s, float) and not isinstance(s, jax.Array)
        assert s == pytest.approx(4.0)


SHARDMAP_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import functools
    import jax, jax.numpy as jnp
    from repro.comm import (RingConfig, make_ring_allreduce,
                            ring_allreduce_nsd)
    from repro.launch import make_mesh
    mesh = make_mesh((8,), ("nodes",))
    key = jax.random.PRNGKey(0)
    gs = jnp.stack([jax.random.normal(jax.random.fold_in(key, i), (37, 13))
                    for i in range(8)])
    fn = make_ring_allreduce(mesh, "nodes", RingConfig(s=1.0))
    means, wires, bounds = fn(gs, key)
    # the sim is jitted for the comparison: eager XLA fuses elementwise
    # chains differently (1-ulp FMA artifacts); per-hop math is identical
    sim = jax.jit(functools.partial(ring_allreduce_nsd, cfg=RingConfig(s=1.0)))
    sim_mean, tele = sim(gs, key)
    # every node must hold the identical result...
    for i in range(1, 8):
        assert float(jnp.max(jnp.abs(means[i] - means[0]))) == 0.0
    # ...bit-exactly equal to the simulation (same hop math, same keys;
    # each hop's output is the next hop's input, so final-state equality
    # transitively pins every intermediate hop)
    assert float(jnp.max(jnp.abs(means[0] - sim_mean))) == 0.0
    assert float(jnp.sum(wires)) == float(tele.wire_bytes)
    # per-hop delta accounting must agree with the sim's error bound too
    assert abs(float(bounds[0]) - float(tele.error_bound)) < 1e-6
    # a caller's mesh with Explicit axes (jax.make_mesh's default) gives
    # the same result, and its output indexes like any array
    means_x, _, _ = make_ring_allreduce(
        jax.make_mesh((8,), ("nodes",)), "nodes", RingConfig(s=1.0))(gs, key)
    assert float(jnp.max(jnp.abs(means_x[0] - sim_mean))) == 0.0
    # dispatcher: telemetry populated and node-count mismatch rejected
    from repro.comm import allreduce_compressed
    mean_d, tele_d = allreduce_compressed(gs, key, RingConfig(s=1.0),
                                          mesh=mesh, axis_name="nodes")
    assert float(jnp.max(jnp.abs(mean_d - sim_mean))) == 0.0
    assert float(tele_d.dense_bytes) == float(tele.dense_bytes)
    assert float(tele_d.error_bound) > 0.0
    assert tele_d.packs_per_segment == 8
    try:
        allreduce_compressed(gs[:3], key, mesh=mesh, axis_name="nodes")
    except ValueError:
        pass
    else:
        raise AssertionError("node/mesh mismatch not rejected")
    print("SHARDMAP_RING_OK", float(jnp.sum(wires)))
""")


def test_shardmap_ring_subprocess():
    """The real compressed exchange: packed NSD pytrees cross (virtual)
    device boundaries via ppermute and agree with the simulation."""
    env = dict(os.environ, PYTHONPATH=os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src")))
    out = subprocess.run([sys.executable, "-c", SHARDMAP_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert "SHARDMAP_RING_OK" in out.stdout, out.stdout + out.stderr


@pytest.mark.skipif(jax.device_count() < 8,
                    reason="needs 8 (virtual) devices — run under "
                           "XLA_FLAGS=--xla_force_host_platform_device_"
                           "count=8 (the CI comm job does)")
def test_ring_shardmap_inprocess(key):
    """In-process sim-vs-shard_map differential for the multi-device CI
    job: bit-exact mean, identical wire bytes and per-hop Delta sums."""
    import functools

    from repro.comm import make_ring_allreduce
    from repro.launch import make_mesh
    mesh = make_mesh((8,), ("nodes",))
    gs = jnp.stack([jax.random.normal(jax.random.fold_in(key, i), (129,))
                    for i in range(8)])
    means, wires, bounds = make_ring_allreduce(
        mesh, "nodes", RingConfig(s=1.0))(gs, key)
    sim_mean, tele = jax.jit(functools.partial(
        ring_allreduce_nsd, cfg=RingConfig(s=1.0)))(gs, key)
    assert float(jnp.max(jnp.abs(means[0] - sim_mean))) == 0.0
    assert float(jnp.sum(wires)) == float(tele.wire_bytes)
    assert abs(float(bounds[0]) - float(tele.error_bound)) < 1e-6
