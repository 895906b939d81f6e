"""repro.memory: residual codecs, per-layer memory policy, the rewired
custom_vjp residual store, remat, byte accounting, and the zero-recompile
pin for codec selection under knob schedules."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (DitherCtx, DitherPolicy, PolicyProgram, Piecewise,
                        conv2d, dense, dithered_einsum, nsd,
                        quantize_cotangent)
from repro.obs import metrics as statslib
from repro.memory import (DEFAULT_NSD_S, MemoryPolicy, MemoryRule,
                          capacity_bytes, decode, dense_nbytes, encode,
                          footprint_totals, measured_bytes,
                          parse_memory_program, parse_mode, resid_key,
                          residual_report, stored_nbytes)


@pytest.fixture
def act(key):
    """A relu-activation-like residual (what the layers actually save)."""
    return jax.nn.relu(jax.random.normal(key, (16, 48), jnp.float32))


class TestCodecs:
    def test_fp32_is_identity(self, act, key):
        enc = encode("fp32", act, key)
        assert enc is act
        assert decode("fp32", enc) is act

    def test_bf16_round_trip(self, act, key):
        dec = decode("bf16", encode("bf16", act, key))
        assert dec.dtype == act.dtype and dec.shape == act.shape
        np.testing.assert_array_equal(
            np.asarray(dec), np.asarray(act.astype(jnp.bfloat16)
                                        .astype(jnp.float32)))

    @pytest.mark.parametrize("shape", [(16, 48), (4, 5, 7), (3, 8, 8, 6)])
    def test_nsd_bit_exact_vs_reference(self, key, shape):
        """The acceptance bar: encode->decode == nsd_quantize for the same
        key, with zero tolerance — incl. shapes that exercise padding."""
        x = jax.random.normal(key, shape, jnp.float32)
        k = resid_key(key)
        dec = decode("nsd", encode("nsd", x, k))
        ref = nsd.nsd_quantize(x, k, DEFAULT_NSD_S)
        assert dec.shape == x.shape and dec.dtype == x.dtype
        np.testing.assert_array_equal(np.asarray(dec), np.asarray(ref))

    def test_nsd_scale_parameter(self, act, key):
        k = resid_key(key)
        dec = decode("nsd@0.5", encode("nsd@0.5", act, k))
        np.testing.assert_array_equal(
            np.asarray(dec), np.asarray(nsd.nsd_quantize(act, k, 0.5)))

    def test_int8_error_bounded_by_half_scale(self, key):
        x = jax.random.normal(key, (32, 64), jnp.float32) * 5.0
        enc = encode("int8", x, key)
        err = jnp.abs(decode("int8", enc) - x).reshape(-1, 64)
        assert float(jnp.max(err / (enc.scale / 2.0))) <= 1.001

    def test_int8_constant_row_exact(self, key):
        x = jnp.full((4, 16), 3.25, jnp.float32)
        dec = decode("int8", encode("int8", x, key))
        np.testing.assert_allclose(np.asarray(dec), np.asarray(x), rtol=0,
                                   atol=0)

    def test_int8_restores_shape_dtype(self, key):
        x = jax.random.normal(key, (2, 3, 4, 5), jnp.bfloat16)
        dec = decode("int8", encode("int8", x, key))
        assert dec.shape == x.shape and dec.dtype == x.dtype

    def test_stored_bytes_ordering(self):
        shape, dt = (64, 256), jnp.float32
        dense = dense_nbytes(shape, dt)
        assert stored_nbytes("fp32", shape, dt) == dense
        assert stored_nbytes("remat", shape, dt) == dense
        assert stored_nbytes("bf16", shape, dt) == dense // 2
        assert stored_nbytes("int8", shape, dt) < dense / 3.5
        assert stored_nbytes("nsd", shape, dt) < dense / 3.5

    def test_nsd_measured_at_most_capacity(self, act, key):
        enc = encode("nsd", act, resid_key(key))
        measured = int(measured_bytes("nsd", enc))
        assert capacity_bytes("nsd", enc) == stored_nbytes(
            "nsd", act.shape, act.dtype)
        assert measured <= capacity_bytes("nsd", enc)

    def test_unknown_mode_rejected(self):
        with pytest.raises(ValueError, match="unknown residual mode"):
            parse_mode("fp64")
        with pytest.raises(ValueError, match="@-parameter"):
            parse_mode("int8@3")
        with pytest.raises(ValueError, match="s must be > 0"):
            parse_mode("nsd@0")
        assert parse_mode("nsd@0.5") == ("nsd", 0.5)


class TestMemoryPolicy:
    def test_last_match_wins_over_default(self):
        pol = MemoryPolicy(default="nsd",
                           rules=(MemoryRule("fc", "int8"),
                                  MemoryRule("fc1", "remat")))
        assert pol.mode_for("fc1") == "remat"
        assert pol.mode_for("fc0") == "int8"
        assert pol.mode_for("conv2") == "nsd"

    def test_glob_pattern(self):
        pol = MemoryPolicy(rules=(MemoryRule("L*.mlp.*", "nsd"),))
        assert pol.mode_for("L3.mlp.up") == "nsd"
        assert pol.mode_for("mlp.up") == "fp32"

    def test_parse_round_trip(self):
        pol = parse_memory_program("default=nsd@0.5;rule fc0:int8;"
                                   "rule c*:remat")
        assert pol.default == "nsd@0.5"
        assert pol.rules == (MemoryRule("fc0", "int8"),
                             MemoryRule("c*", "remat"))
        assert pol.mode_for("c3") == "remat"

    def test_parse_errors(self):
        with pytest.raises(ValueError, match="cannot parse clause"):
            parse_memory_program("bogus")
        with pytest.raises(ValueError, match="rule syntax"):
            parse_memory_program("rule fc0")
        with pytest.raises(ValueError, match="unknown residual mode"):
            parse_memory_program("default=fp64")
        with pytest.raises(ValueError, match=r"MemoryRule\('fc'\)"):
            parse_memory_program("rule fc:fp64")
        # registry-widened grammar: any registered quant codec is a mode
        pol = parse_memory_program("default=int4@g32;rule fc:m8")
        assert pol.default == "int4@g32"

    def test_policy_is_hashable(self):
        a = parse_memory_program("default=nsd;rule fc:int8")
        b = parse_memory_program("default=nsd;rule fc:int8")
        assert hash(a) == hash(b) and {a: 1}[b] == 1


def _grad_fn(x, pol, mem, name="fc"):
    def grads(w):
        ctx = DitherCtx.for_step(jax.random.PRNGKey(0), 3, pol, memory=mem)
        return jax.grad(lambda xw: jnp.sum(
            dense(xw[0], xw[1], ctx=ctx, name=name) ** 2))((x, w))
    return grads


class TestResidualStore:
    """The rewired custom_vjp: fwd encodes, bwd decodes."""

    def test_fp32_mode_bit_identical_to_no_policy(self, key, act):
        w = jax.random.normal(key, (48, 8)) * 0.1
        pol = DitherPolicy(variant="paper", s=2.0)
        g_none = _grad_fn(act, pol, None)(w)
        g_fp32 = _grad_fn(act, pol, MemoryPolicy(default="fp32"))(w)
        for a, b in zip(g_none, g_fp32):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_remat_bit_identical_to_store(self, key, act):
        """Recompute-in-VJP must reproduce the stored-residual grads
        exactly (same keys -> same dither draws)."""
        w = jax.random.normal(key, (48, 8)) * 0.1
        pol = DitherPolicy(variant="paper", s=2.0)
        g_none = _grad_fn(act, pol, None)(w)
        g_rm = _grad_fn(act, pol, MemoryPolicy(default="remat"))(w)
        for a, b in zip(g_none, g_rm):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @pytest.mark.parametrize("variant", ["paper", "int8"])
    def test_nsd_residual_touches_only_dw(self, key, act, variant):
        """dx = g~ . W^T never reads x: it is bit-identical across residual
        modes; dW sees exactly the decoded (quantized) activations."""
        w = jax.random.normal(key, (48, 8)) * 0.1
        pol = DitherPolicy(variant=variant, s=2.0)
        dx0, _ = _grad_fn(act, pol, None)(w)
        dxn, _ = _grad_fn(act, pol, MemoryPolicy(default="nsd"))(w)
        np.testing.assert_array_equal(np.asarray(dx0), np.asarray(dxn))

    def test_nsd_residual_dw_matches_manual_product(self, key, act):
        """dW under the nsd codec == decode(encode(x))^T @ g~ computed by
        hand from the same keys — pins both the codec wiring and the RNG
        stream separation (RESID_SALT)."""
        w = jax.random.normal(key, (48, 8)) * 0.1
        pol = DitherPolicy(variant="paper", s=2.0)
        _, dw = _grad_fn(act, pol, MemoryPolicy(default="nsd"))(w)

        ctx = DitherCtx.for_step(jax.random.PRNGKey(0), 3, pol)
        layer_key = ctx.key_for("fc")
        y = act @ w
        g = 2.0 * y  # cotangent of sum(y**2)
        gq = quantize_cotangent(g, layer_key, pol.knobs(), pol.spec(), "fc")
        x_hat = nsd.nsd_quantize(act, resid_key(layer_key), DEFAULT_NSD_S)
        x_hat, gq = np.asarray(x_hat), np.asarray(gq)
        # the two products may sum in different orders (XLA fuses the
        # jitted one differently): allow the f32 rounding bound of a
        # length-T dot product, T * eps * sum_t |x_t g_t|, per entry
        ulp_bound = (x_hat.shape[0] * np.finfo(np.float32).eps
                     * (np.abs(x_hat).T @ np.abs(gq)))
        err = np.abs(np.asarray(dw) - x_hat.T @ gq)
        assert np.all(err <= ulp_bound), float(np.max(err - ulp_bound))

    def test_conv_and_einsum_modes(self, key):
        x = jax.random.normal(key, (2, 8, 8, 3))
        w = jax.random.normal(jax.random.fold_in(key, 1), (3, 3, 3, 4)) * 0.1
        pol = DitherPolicy(variant="paper", s=2.0)

        def grads(mem):
            ctx = DitherCtx.for_step(jax.random.PRNGKey(0), 1, pol,
                                     memory=mem)
            return jax.grad(lambda xw: jnp.sum(conv2d(
                xw[0], xw[1], ctx=ctx, name="c1") ** 2))((x, w))

        dx0, dw0 = grads(None)
        for mode in ("nsd", "int8", "bf16", "remat"):
            dxm, dwm = grads(MemoryPolicy(default=mode))
            # conv dx pulls back through w only: exact in every mode
            np.testing.assert_array_equal(np.asarray(dx0), np.asarray(dxm))
            assert np.all(np.isfinite(np.asarray(dwm)))
        xe = jax.random.normal(key, (4, 6, 8))
        we = jax.random.normal(jax.random.fold_in(key, 2), (8, 5)) * 0.1
        ctx = DitherCtx.for_step(jax.random.PRNGKey(0), 1, pol,
                                 memory=MemoryPolicy(default="nsd"))
        g = jax.grad(lambda w: jnp.sum(dithered_einsum(
            "bte,eh->bth", xe, w, ctx=ctx, name="ein") ** 2))(we)
        assert np.all(np.isfinite(np.asarray(g)))

    def test_per_layer_rules_resolve_statically(self, key):
        pol = DitherPolicy(variant="paper", s=2.0)
        mem = parse_memory_program("default=nsd;rule fc2:fp32")
        ctx = DitherCtx.for_step(key, 0, pol, memory=mem)
        assert ctx.resolve("fc1").spec.residual == "nsd"
        assert ctx.resolve("fc2").spec.residual == "fp32"
        # and through a program path
        prog = PolicyProgram(base=pol)
        ctx2 = DitherCtx.for_step(key, 0, pol, program=prog, memory=mem)
        assert ctx2.resolve("fc1").spec.residual == "nsd"

    def test_remat_strips_telemetry(self, key):
        """io effects can't cross jax.checkpoint: remat resolution keeps
        collect_stats on the spec, the op wrapper strips it (pinned here
        via the emitted rows: memory row yes, sparsity row no)."""
        statslib.reset()
        pol = DitherPolicy(variant="paper", s=2.0, collect_stats=True,
                           stats_tag="rm/")
        x = jax.random.normal(key, (8, 16))
        w = jax.random.normal(jax.random.fold_in(key, 1), (16, 4)) * 0.1
        ctx = DitherCtx.for_step(key, 0, pol,
                                 memory=MemoryPolicy(default="remat"))
        jax.grad(lambda w: jnp.sum(dense(x, w, ctx=ctx, name="fc") ** 2))(w)
        rows = statslib.memory_rows("rm/fc")
        assert rows.shape == (1, 3)
        assert rows[0, 0] == rows[0, 1] == rows[0, 2]  # raw-input store
        assert statslib.row_count("rm/fc") == 0  # no sparsity telemetry

    @pytest.mark.parametrize("mode", ["nsd", "remat"])
    def test_no_memory_rows_without_differentiation(self, key, mode):
        """Telemetry fires only when a backward will consume the residual:
        a plain (un-differentiated) forward with a collect_stats ctx emits
        nothing, for codec AND remat layers alike."""
        statslib.reset()
        pol = DitherPolicy(variant="paper", s=2.0, collect_stats=True,
                           stats_tag="nd/")
        x = jax.random.normal(key, (8, 16))
        w = jax.random.normal(jax.random.fold_in(key, 1), (16, 4)) * 0.1
        ctx = DitherCtx.for_step(key, 0, pol,
                                 memory=MemoryPolicy(default=mode))
        dense(x, w, ctx=ctx, name="fc").block_until_ready()
        assert statslib.memory_tags() == []


class TestCompileCounter:
    def test_codec_adds_zero_recompiles_under_s_ramp(self, key):
        """The acceptance pin: codec selection is static per layer, so a
        scheduled s ramp still compiles exactly once."""
        x = jax.random.normal(key, (8, 16))
        prog = PolicyProgram(
            base=DitherPolicy(variant="paper", collect_stats=True,
                              stats_tag="mc/"),
            s=Piecewise(((0, 1.0), (2, 2.0), (4, 4.0))))
        mem = parse_memory_program("default=nsd;rule fc2:int8")
        traces = []

        @jax.jit
        def step(w, i, k):
            traces.append(1)
            ctx = DitherCtx.for_step(k, i, prog.base, program=prog,
                                     memory=mem)

            def loss(w):
                h = dense(x, w["w1"], ctx=ctx, name="fc1")
                return jnp.sum(dense(h, w["w2"], ctx=ctx, name="fc2") ** 2)

            g = jax.grad(loss)(w)
            return jax.tree.map(lambda a, b: a - 0.01 * b, w, g)

        statslib.reset()
        w = {"w1": jax.random.normal(key, (16, 24)) * 0.1,
             "w2": jax.random.normal(jax.random.fold_in(key, 1),
                                     (24, 8)) * 0.1}
        for i in range(6):
            w = step(w, jnp.int32(i), key)
        assert len(traces) == 1, f"codec + s ramp retraced {len(traces)}x"
        # the ramp took effect under the codec path
        jax.effects_barrier()
        deltas = statslib.rows("mc/fc1")[:, 2]
        assert len(np.unique(np.round(deltas / deltas[0], 3))) >= 3

    def test_memory_policy_change_retraces(self, key):
        """Flipping the (static) codec IS a retrace — exactly once."""
        x = jax.random.normal(key, (8, 16))
        w = jax.random.normal(jax.random.fold_in(key, 1), (16, 4)) * 0.1
        pol = DitherPolicy(variant="paper", s=2.0)
        traces = []

        def step(w, mem):
            traces.append(1)
            ctx = DitherCtx.for_step(jax.random.PRNGKey(0), 0, pol,
                                     memory=mem)
            return jax.grad(lambda w: jnp.sum(
                dense(x, w, ctx=ctx, name="fc") ** 2))(w)

        jit_step = jax.jit(step, static_argnames=("mem",))
        for mem in (MemoryPolicy(default="fp32"),
                    MemoryPolicy(default="nsd"),
                    MemoryPolicy(default="nsd")):
            jit_step(w, mem)
        assert len(traces) == 2


class TestAccounting:
    def _loss(self, p, b, ctx):
        h = dense(b, p["w1"], ctx=ctx, name="fc1")
        return jnp.sum(dense(h, p["w2"], ctx=ctx, name="fc2") ** 2)

    def test_report_and_totals(self):
        params = {"w1": jnp.zeros((64, 32)), "w2": jnp.zeros((32, 8))}
        batch = jnp.zeros((16, 64))
        mem = parse_memory_program("default=nsd;rule fc2:remat")
        rep = residual_report(self._loss, params, batch, memory=mem)
        assert set(rep) == {"fc1", "fc2"}
        assert rep["fc1"] == (stored_nbytes("nsd", (16, 64), jnp.float32),
                              16 * 64 * 4)
        assert rep["fc2"] == (16 * 32 * 4, 16 * 32 * 4)  # remat: dense
        stored, dense_b = footprint_totals(rep)
        assert stored < dense_b

    def test_no_memory_policy_reports_dense(self):
        params = {"w1": jnp.zeros((64, 32)), "w2": jnp.zeros((32, 8))}
        rep = residual_report(self._loss, params, jnp.zeros((4, 64)))
        stored, dense_b = footprint_totals(rep)
        assert stored == dense_b > 0

    def test_off_policy_reports_nothing(self):
        params = {"w1": jnp.zeros((64, 32)), "w2": jnp.zeros((32, 8))}
        rep = residual_report(self._loss, params, jnp.zeros((4, 64)),
                              policy=DitherPolicy(variant="off"))
        assert rep == {}

    def test_price_memory(self):
        from repro.launch.costmodel import price_memory
        out = price_memory(1e9, 4e9, n_chips=4, batch=8,
                           fixed_bytes_per_chip=8e9, hbm_bytes=16e9)
        assert out["residual_compression"] == pytest.approx(4.0)
        # dense: 1e9/chip residual, 8e9 headroom -> batch 8 * 8 = 64
        assert out["est_max_batch_dense"] == pytest.approx(64.0)
        assert out["est_max_batch_stored"] == pytest.approx(256.0)


class TestTelemetryAndHarness:
    def test_memory_rows_and_compression(self, key):
        statslib.reset()
        pol = DitherPolicy(variant="paper", s=2.0, collect_stats=True,
                           stats_tag="mt/")
        x = jax.nn.relu(jax.random.normal(key, (16, 64)))
        w = jax.random.normal(jax.random.fold_in(key, 1), (64, 8)) * 0.1
        ctx = DitherCtx.for_step(key, 0, pol,
                                 memory=MemoryPolicy(default="nsd"))
        for _ in range(2):
            jax.grad(lambda w: jnp.sum(
                dense(x, w, ctx=ctx, name="fc") ** 2))(w)
        rows = statslib.memory_rows("mt/fc")
        assert rows.shape == (2, 3)
        # measured occupancy <= HBM capacity <= dense, rowwise
        assert np.all(rows[:, 0] <= rows[:, 1]) and np.all(
            rows[:, 1] <= rows[:, 2])
        assert statslib.overall_residual_compression("mt/") > 3.5
        assert statslib.overall_residual_compression(
            "mt/", capacity=True) > 3.0
        summ = statslib.memory_summary()["mt/fc"]
        assert summ["occupancy_compression"] > 3.5
        assert summ["capacity_compression"] > 3.0
        assert summ["n_records"] == 2

    def test_train_classifier_with_memory(self):
        from repro.configs import paper_models as pm
        from benchmarks.harness import train_classifier
        pol = DitherPolicy(variant="paper", s=2.0, collect_stats=True,
                           stats_tag="th/")
        out = train_classifier(pm.lenet300100(), pol, steps=3,
                               memory="default=nsd")
        assert np.isfinite(out["acc"])
        assert out["residual_compression"] > 3.5


class TestStaticSpecResidual:
    def test_default_is_fp32(self):
        assert DitherPolicy().spec().residual == "fp32"

    def test_with_key_preserves_memory(self, key):
        mem = MemoryPolicy(default="nsd")
        ctx = DitherCtx.for_step(key, 0, DitherPolicy(), memory=mem)
        clone = ctx.with_key(jax.random.fold_in(key, 9))
        assert clone.memory is mem
