"""The quant engine: registry front door, facade bit-exactness against the
legacy entry points (now deprecation shims), the new codecs (int4 grouped,
m8/u8 moments), compute-on-packed, grad_codec threading, and the
``quant:`` launcher DSL section."""
import dataclasses
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import quant
from repro.core import DitherCtx, DitherPolicy, dense, int8 as int8lib, nsd
from repro.optim import OptConfig, apply_updates, init_opt_state
from repro.quant import (QuantSpec, codec_names, decode, dense_nbytes,
                         encode, error_bound, get_codec, measured_bytes,
                         parse_quant_program, parse_spec, quantize,
                         resid_key, stored_nbytes, validate_spec)


class TestRegistry:
    def test_all_builtins_registered(self):
        assert set(codec_names()) >= {"fp32", "remat", "bf16", "int8", "nsd",
                                      "int8_absmax", "int4", "m8", "u8"}

    def test_parse_spec_is_cached_and_canonical(self):
        s1 = parse_spec("nsd@0.5")
        assert s1 is parse_spec("nsd@0.5")  # lru_cache
        assert s1.mode == "nsd@0.5"
        assert parse_spec("int4@g64").mode == "int4@g64"
        assert parse_spec("int4").group == quant.DEFAULT_INT4_GROUP

    def test_unknown_codec_names_the_registry(self):
        with pytest.raises(ValueError, match="unknown codec"):
            validate_spec("fp64")

    def test_duplicate_registration_rejected(self):
        with pytest.raises(ValueError, match="already registered"):
            quant.register(get_codec("int8"))

    def test_spec_is_static_and_hashable(self):
        spec = parse_spec("int4@g32")
        assert isinstance(spec, QuantSpec)
        assert hash(spec) == hash(spec.replace())


class TestLegacyPins:
    """The old entry points are shims over repro.quant — bit-exact."""

    def test_memory_codec_shim_reexports_same_objects(self):
        import repro.memory.codec as legacy

        assert legacy.encode is quant.encode
        assert legacy.decode is quant.decode
        assert legacy.parse_mode is quant.parse_mode

    def test_comm_wireformat_shim_reexports_same_objects(self):
        import repro.comm.wireformat as legacy

        assert legacy.pack_nsd is quant.wire.pack_nsd
        assert legacy.unpack_nsd is quant.wire.unpack_nsd

    def test_shim_modules_warn_on_import(self):
        import repro.comm.wireformat as wf_shim
        import repro.memory.codec as mem_shim

        for mod in (mem_shim, wf_shim):
            with pytest.deprecated_call():
                importlib.reload(mod)

    def test_nsd_quantize_warns_and_matches_quant(self, key):
        x = jax.random.normal(key, (16, 48))
        with pytest.deprecated_call():
            ref = nsd.nsd_quantize(x, key, 1.5)
        np.testing.assert_array_equal(
            np.asarray(quant.nsd_fakequant(x, key, 1.5)), np.asarray(ref))

    def test_quantize_int8_warns_and_matches_quant(self, key):
        x = jax.random.normal(key, (16, 48))
        with pytest.deprecated_call():
            q_ref, s_ref = int8lib.quantize_int8(x)
        q, s = quant.absmax_int8(x)
        np.testing.assert_array_equal(np.asarray(q), np.asarray(q_ref))
        assert float(s) == float(s_ref)

    def test_nsd_mode_bit_exact_through_registry(self, key):
        """Registry dispatch adds nothing: decode(encode()) == reference."""
        x = jax.nn.relu(jax.random.normal(key, (13, 77)))
        k = resid_key(jax.random.fold_in(key, 1))
        dec = decode("nsd@2", encode("nsd@2", x, k))
        np.testing.assert_array_equal(
            np.asarray(dec), np.asarray(quant.nsd_fakequant(x, k, 2.0)))


class TestErrorBounds:
    @pytest.mark.parametrize("mode", ["bf16", "int8", "int8_absmax",
                                      "int4@g32", "int4@g64", "m8"])
    def test_roundtrip_within_bound(self, key, mode):
        x = jax.random.normal(key, (24, 96)) * 5.0
        enc = encode(mode, x, key)
        err = jnp.abs(decode(mode, enc) - x)
        bound = error_bound(mode, enc)
        assert float(jnp.max(err / (bound + 1e-12))) <= 1.0 + 1e-4

    def test_nsd_bound_is_delta_where_it_holds(self, key):
        x = jax.random.normal(key, (24, 96)) * 5.0
        enc = encode("nsd@2", x, key)
        bound = error_bound("nsd@2", enc)
        np.testing.assert_array_equal(
            np.asarray(bound),
            np.full(x.shape, float(enc.deltas[0]), np.float32))
        assert float(jnp.max(jnp.abs(decode("nsd@2", enc) - x) - bound)) <= 0

    @pytest.mark.parametrize("x", [[1.6], [1.0, 1.001]],
                             ids=["one_element_delta_0", "level_clipped"])
    def test_nsd_bound_inf_where_nothing_bounds_it(self, key, x):
        """One element has std 0, so Delta is 0 and it decodes to 0; two
        near-equal values have max/std far over 127/s, so a level clips.
        Neither error is bounded by Delta, and the bound says so."""
        x = jnp.asarray(x, jnp.float32)
        enc = encode("nsd@0.5", x, key)
        err = jnp.abs(decode("nsd@0.5", enc) - x)
        bound = error_bound("nsd@0.5", enc)
        assert bool(jnp.any(err > enc.deltas[0]))
        assert bool(jnp.all(err <= bound)), (err, bound)

    def test_u8_bound_in_squared_domain(self, key):
        v = jnp.square(jax.random.normal(key, (8, 64)) * 3.0)
        enc = encode("u8", v, key)
        err = jnp.abs(decode("u8", enc) - v)
        assert float(jnp.max(err / (error_bound("u8", enc) + 1e-12))) <= 1.0 + 1e-4
        assert float(jnp.min(decode("u8", enc))) >= 0.0

    def test_exact_modes_have_no_bound(self, key):
        x = jax.random.normal(key, (4, 4))
        for mode in ("fp32", "remat"):
            assert error_bound(mode, encode(mode, x, key)) is None


class TestInt4Grouped:
    def test_grammar(self):
        assert parse_spec("int4@g32") == parse_spec("int4@32")
        with pytest.raises(ValueError):
            validate_spec("int4@g0")
        with pytest.raises(ValueError):
            validate_spec("int4@gx")

    def test_stored_bytes_formula(self):
        # 8x64 = 512 elems, g=32 -> 16 groups: 16*16 nibble bytes + 16*4 scale
        assert stored_nbytes("int4@g32", (8, 64), jnp.float32) == 16 * 16 + 64
        assert dense_nbytes((8, 64), jnp.float32) == 2048

    def test_non_multiple_shape_roundtrips(self, key):
        x = jax.random.normal(key, (5, 13))  # 65 elems, g=32 -> padded
        enc = encode("int4@g32", x, key)
        dec = decode("int4@g32", enc)
        assert dec.shape == x.shape and dec.dtype == x.dtype
        bound = error_bound("int4@g32", enc)
        assert float(jnp.max(jnp.abs(dec - x) / (bound + 1e-12))) <= 1.0 + 1e-4

    def test_all_zero_is_exact(self, key):
        x = jnp.zeros((4, 32))
        np.testing.assert_array_equal(
            np.asarray(decode("int4@g32", encode("int4@g32", x, key))),
            np.zeros((4, 32), np.float32))


class TestComputeOnPacked:
    def test_nsd_jnp_backend_matches_decode_matmul(self, key):
        g = jax.random.normal(key, (16, 128))
        x = jax.random.normal(jax.random.fold_in(key, 1), (16, 64))
        w = jax.random.normal(jax.random.fold_in(key, 2), (64, 128))
        enc = encode("nsd", g, key)
        dx, dw = get_codec("nsd").compute_on_packed(
            parse_spec("nsd"), enc, x, w, backend="jnp")
        g_hat = decode("nsd", enc)
        np.testing.assert_allclose(np.asarray(dx), np.asarray(g_hat @ w.T),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(dw), np.asarray(x.T @ g_hat),
                                   rtol=1e-5, atol=1e-5)


class TestGradCodec:
    def test_policy_validates_spec(self):
        with pytest.raises(ValueError, match="unknown codec"):
            DitherPolicy(variant="paper", grad_codec="fp64")

    def test_fp32_grad_codec_recovers_plain_backprop(self, key):
        """grad_codec replaces the variant's NSD quantizer; the identity
        codec therefore yields EXACTLY the undithered gradient."""
        x = jax.random.normal(key, (8, 16))
        w = jax.random.normal(jax.random.fold_in(key, 1), (16, 24)) * 0.1

        def g(policy):
            ctx = (DitherCtx.for_step(key, 0, policy)
                   if policy is not None else None)
            return jax.grad(lambda w: jnp.sum(
                jnp.sin(dense(x, w, ctx=ctx, name="fc"))))(w)

        g_plain = g(None)
        g_fp32 = g(DitherPolicy(variant="paper", s=2.0, grad_codec="fp32"))
        np.testing.assert_array_equal(np.asarray(g_fp32), np.asarray(g_plain))

    def test_registry_codec_on_cotangent(self, key):
        """dw == x^T @ codec(g): eq. 9 with the registry codec swapped in."""
        x = jax.random.normal(key, (8, 16))
        w = jax.random.normal(jax.random.fold_in(key, 1), (16, 24)) * 0.1
        pol = DitherPolicy(variant="paper", s=2.0, grad_codec="int4@g32")
        ctx = DitherCtx.for_step(key, 0, pol)

        def loss(w):
            return jnp.sum(jnp.sin(dense(x, w, ctx=ctx, name="fcQ")))

        gw = jax.grad(loss)(w)
        g = jnp.cos(x @ w)
        gq = quantize("int4@g32", g, ctx.key_for("fcQ"))
        np.testing.assert_allclose(np.asarray(gw), np.asarray(x.T @ gq),
                                   rtol=1e-4, atol=1e-5)

    def test_program_base_carries_grad_codec(self, key):
        from repro.core.schedule import parse_program

        base = DitherPolicy(variant="paper", s=2.0, grad_codec="int8_absmax")
        prog = parse_program("rule other:off", base=base)
        ctx = DitherCtx.for_step(key, 0, base, program=prog)
        r = ctx.resolve("fc0")
        assert r is not None and r.spec.grad_codec == "int8_absmax"


class TestMomentCodecs:
    def _run(self, cfg, steps=5, key=None):
        key = key if key is not None else jax.random.PRNGKey(0)
        params = {"w": jax.random.normal(key, (8, 8)) * 0.1}
        state = init_opt_state(params, cfg)
        for i in range(steps):
            grads = {"w": jax.random.normal(jax.random.fold_in(key, i),
                                            (8, 8))}
            params, state, _ = apply_updates(params, grads, state, cfg)
        return params, state

    def test_needs_key_codec_rejected(self):
        with pytest.raises(ValueError, match="deterministic"):
            OptConfig(mu_codec="nsd")

    def test_adamw_encoded_moments_step(self):
        cfg = OptConfig(name="adamw", lr=1e-2, mu_codec="m8", nu_codec="u8")
        params, state = self._run(cfg)
        assert isinstance(state["mu"]["w"], quant.RowQuant8)
        assert isinstance(state["nu"]["w"], quant.SqrtRowQuant8)
        assert np.isfinite(np.asarray(params["w"])).all()

    def test_sgd_encoded_momentum_tracks_fp32(self):
        key = jax.random.PRNGKey(3)
        dense_cfg = OptConfig(name="sgd", lr=1e-2, grad_clip=None)
        enc_cfg = dataclasses.replace(dense_cfg, mu_codec="m8")
        p_dense, _ = self._run(dense_cfg, key=key)
        p_enc, _ = self._run(enc_cfg, key=key)
        # 8-bit row-quantized momentum: same trajectory to ~1% of movement
        moved = float(jnp.max(jnp.abs(p_dense["w"])))
        drift = float(jnp.max(jnp.abs(p_dense["w"] - p_enc["w"])))
        assert drift <= 0.05 * max(moved, 1e-6), (drift, moved)

    def test_state_specs_match_encoded_structure(self):
        from repro.optim import opt_state_specs

        cfg = OptConfig(name="adamw", mu_codec="m8", nu_codec="u8")
        params = {"w": jnp.zeros((4, 4)), "b": jnp.zeros((4,))}
        state = init_opt_state(params, cfg)
        specs = opt_state_specs({"w": ("a", "b"), "b": ("a",)}, cfg)
        # one spec leaf (None = replicated) per encoded-container leaf, so
        # sharded dry-runs can zip the two trees positionally
        n_state = len(jax.tree.leaves(state))
        n_specs = len(jax.tree.leaves(specs,
                                      is_leaf=lambda x: x is None))
        assert n_state == n_specs, (n_state, n_specs)


class TestCommRegistryModes:
    def test_compress_leaf_any_registered_codec(self, key):
        from repro.comm import CommPolicy
        from repro.comm.compression import compress_leaf

        g = jax.random.normal(key, (32, 64))
        pol = CommPolicy(default="int4@g32")
        g_hat, nbytes, _ = compress_leaf(g, key, "int4@g32", pol, None)
        enc = encode("int4@g32", g, key)
        np.testing.assert_array_equal(
            np.asarray(g_hat), np.asarray(decode("int4@g32", enc)))
        assert int(nbytes) == int(measured_bytes("int4@g32", enc))

    def test_policy_rejects_unknown_mode(self):
        from repro.comm import CommPolicy

        with pytest.raises(ValueError, match="unknown comm mode"):
            CommPolicy(default="fp64")


class TestKVRegistryModes:
    def test_init_paged_accepts_registered_spec(self, key):
        from repro.serve.kvcache import init_paged

        init_paged("nsd@1", batch=1, max_len=16, n_pages=2, page=8,
                   n_kv=1, hd=4, dtype=jnp.float32, key=key)

    def test_init_paged_rejects_unknown(self, key):
        from repro.serve.kvcache import init_paged

        with pytest.raises(ValueError, match="kv mode"):
            init_paged("fp64", batch=1, max_len=16, n_pages=2, page=8,
                       n_kv=1, hd=4, dtype=jnp.float32, key=key)


class TestQuantProgramDSL:
    def test_parse_and_roundtrip(self):
        qp = parse_quant_program("grad=int4@g32;mu=m8;nu=u8")
        assert (qp.grad, qp.mu, qp.nu) == ("int4@g32", "m8", "u8")
        assert qp.wire is None and qp.resid is None
        assert quant.format_quant_program(qp) == "grad=int4@g32;mu=m8;nu=u8"
        assert not parse_quant_program("")

    def test_errors(self):
        with pytest.raises(ValueError, match="cannot parse quant clause"):
            parse_quant_program("kv=int8")
        with pytest.raises(ValueError, match="unknown codec"):
            parse_quant_program("grad=fp64")
        with pytest.raises(ValueError, match="deterministic"):
            parse_quant_program("mu=nsd@1")
        with pytest.raises(ValueError, match="duplicate"):
            parse_quant_program("grad=int8;grad=int8")

    def test_launch_program_quant_section(self):
        from repro.launch.program import format_program, parse_program

        spec = parse_program("dither: rule a:off quant: grad=int8_absmax")
        assert spec.quant == "grad=int8_absmax"
        assert spec.quant_overrides().grad == "int8_absmax"
        assert parse_program(format_program(spec)) == spec

    def test_importing_owners_is_warning_free(self):
        """Only the LEGACY entry points warn; the migrated owners must not
        (a regression here means someone re-imported a shim)."""
        import subprocess
        import sys

        code = ("import warnings; warnings.simplefilter('error', "
                "DeprecationWarning); import repro.core, repro.comm, "
                "repro.memory, repro.quant, repro.serve.kvcache, "
                "repro.launch.program, repro.optim")
        proc = subprocess.run([sys.executable, "-c", code],
                              capture_output=True, text=True)
        assert proc.returncode == 0, proc.stderr
