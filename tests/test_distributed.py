"""Distributed dithered training (paper §3.6/§4.3): noise cancellation with
N nodes, s(N) scaling, comm-compression analogues, sharded pjit step."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_model
from repro.core import DitherPolicy, nsd
from repro.obs import metrics as statslib
from repro.distributed import (SSGDConfig, int8_allreduce_sim, make_ssgd_step,
                               shard_batch, topk_error_feedback)
from repro.optim import OptConfig, init_opt_state


def _tiny_lm():
    return get_smoke_model("mamba2-370m")


class TestSSGD:
    def test_noise_cancels_with_more_nodes(self, key):
        """Variance of the server-side averaged gradient drops with N (the
        paper's cancellation argument), at FIXED s and FIXED per-node batch.

        Weak scaling is essential here: per-node Delta is s * std of the
        per-node gradient, so shrinking sub-batches (strong scaling) RAISES
        per-node Delta and the averaging cannot win — the paper's setup is
        each node bringing its own data. The batch is held constant across
        trials, so the trial-to-trial variance isolates the dither noise."""
        model = _tiny_lm()
        params, _ = model.init(key)
        full = {
            "tokens": jax.random.randint(key, (8, 16), 0, model.cfg.vocab),
            "labels": jax.random.randint(key, (8, 16), 0, model.cfg.vocab),
        }
        opt = OptConfig(lr=0.0, grad_clip=None)  # lr 0: inspect grads only

        def avg_grad_var(batch, n_nodes, n_trials=6):
            dcfg = SSGDConfig(n_nodes=n_nodes, s_schedule="fixed", s_base=3.0)
            step_fn, _ = make_ssgd_step(model, opt, dcfg,
                                        DitherPolicy(variant="paper"))
            sb = shard_batch(batch, n_nodes)
            grads = []
            for trial in range(n_trials):
                state = init_opt_state(params, opt)
                bk = jax.random.fold_in(key, 100 + trial)
                _, st, _, _ = step_fn(params, state, sb, bk)
                grads.append(st["mu"])  # momentum buffer == grads at step 1
            flat = [jnp.concatenate([g.reshape(-1) for g in
                                     jax.tree.leaves(t)]) for t in grads]
            stack = jnp.stack(flat)
            return float(jnp.mean(jnp.var(stack, axis=0)))

        # per-node dither noise is independent (per-worker keys), so the
        # server average of 4 nodes has variance mean_i(v_i) / 4, where v_i
        # is node i's own single-node variance on its own shard. Comparing
        # against one shard's v_i alone would make the margin depend on how
        # that shard's Delta compares to the others'.
        v1 = sum(avg_grad_var({k: v[2 * i: 2 * i + 2]
                               for k, v in full.items()}, 1)
                 for i in range(4)) / 4
        v4 = avg_grad_var(full, 4)
        assert v4 < v1 / 2, (v1, v4)

    def test_sparsity_grows_with_nodes(self, key):
        """Paper fig. 6a: s(N) scaling raises per-node sparsity with N."""
        model = _tiny_lm()
        params, _ = model.init(key)
        batch = {
            "tokens": jax.random.randint(key, (8, 16), 0, model.cfg.vocab),
            "labels": jax.random.randint(key, (8, 16), 0, model.cfg.vocab),
        }
        opt = OptConfig(lr=1e-3)
        sparsities = {}
        for n in (1, 4):
            statslib.reset()
            dcfg = SSGDConfig(n_nodes=n, s_schedule="linear", s_base=1.0)
            pol = DitherPolicy(variant="paper", collect_stats=True,
                               stats_tag=f"n{n}/")
            step_fn, used_policy = make_ssgd_step(model, opt, dcfg, pol)
            assert used_policy.s == pytest.approx(n * 1.0)
            state = init_opt_state(params, opt)
            step_fn(params, state, shard_batch(batch, n), key)
            # telemetry arrives via async io_callback: block before reading
            jax.effects_barrier()
            sparsities[n] = statslib.overall_sparsity()
        assert sparsities[4] > sparsities[1], sparsities

    def test_loss_still_decreases_with_dither_at_n4(self, key):
        model = _tiny_lm()
        from repro.data import TokenStreamConfig, token_batch
        tcfg = TokenStreamConfig(vocab=model.cfg.vocab, seq_len=16, batch=8)
        opt = OptConfig(lr=1e-3)
        dcfg = SSGDConfig(n_nodes=4, s_schedule="sqrt", s_base=1.0)
        step_fn, _ = make_ssgd_step(model, opt, dcfg,
                                    DitherPolicy(variant="paper"))
        params, _ = model.init(key)
        state = init_opt_state(params, opt)
        losses = []
        for i in range(25):
            sb = shard_batch(token_batch(tcfg, i), 4)
            params, state, m, _ = step_fn(params, state, sb, key)
            losses.append(float(m["loss"]))
        assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.05, losses


class TestCompression:
    def test_int8_allreduce_error_bounded(self, key):
        gs = [jax.random.normal(jax.random.fold_in(key, i), (1024,))
              for i in range(8)]
        avg = sum(gs) / 8
        approx = int8_allreduce_sim(gs, key)
        delta = float(nsd.compute_delta(gs[0], 1.0))
        err = float(jnp.max(jnp.abs(approx - avg)))
        # unbiased per-node errors, bounded by delta; average shrinks them
        assert err < delta * 2.0

    def test_error_feedback_recovers_mass(self, key):
        g = jax.random.normal(key, (512,))
        state = None
        sent_total = jnp.zeros_like(g)
        for _ in range(50):
            sent, state = topk_error_feedback(g, state, k_frac=0.05)
            sent_total = sent_total + sent
        # after many rounds the cumulative sent mass approximates 50*g;
        # the steady-state residual for always-small coordinates keeps the
        # error away from 0 but it must be bounded and much smaller than
        # plain (no-feedback) top-k, which would lose 1-k_frac of the mass
        rel = float(jnp.linalg.norm(sent_total / 50 - g)
                    / jnp.linalg.norm(g))
        assert rel < 0.3, rel
        no_feedback = 1.0 - 0.05  # mass lost by plain top-k each round
        assert rel < no_feedback / 2


PJIT_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import get_smoke_model
    from repro.core import DitherPolicy
    from repro.launch.steps import make_train_step
    from repro.optim import OptConfig, init_opt_state, opt_state_specs
    from repro.parallel import axes as axlib

    from repro.launch import make_mesh
    mesh = make_mesh((4, 2), ("data", "model"))
    model = get_smoke_model("qwen2.5-32b")
    key = jax.random.PRNGKey(0)
    rules = axlib.tp_dp_rules(mesh)
    with axlib.use_rules(rules):
        params, specs = model.init(key)
        opt_cfg = OptConfig(lr=1e-3)
        opt_state = init_opt_state(params, opt_cfg)
        shardings = axlib.spec_tree_to_shardings(specs, rules, params)
        params = jax.device_put(params, shardings)
        batch = {
            "tokens": jax.random.randint(key, (8, 16), 0, model.cfg.vocab),
            "labels": jax.random.randint(key, (8, 16), 0, model.cfg.vocab),
        }
        batch = {k: jax.device_put(v, NamedSharding(mesh, P("data", None)))
                 for k, v in batch.items()}
        step = jax.jit(make_train_step(model, opt_cfg,
                                       DitherPolicy(variant="paper")))
        p2, o2, m = step(params, opt_state, batch, key)
        p3, o3, m2 = step(p2, o2, batch, key)
    assert float(m2["loss"]) > 0 and float(m2["loss"]) < 20
    # dithered sharded step must equal itself deterministically
    print("PJIT_OK", float(m["loss"]), float(m2["loss"]))
""")


def test_sharded_dithered_train_step_subprocess():
    env = dict(os.environ, PYTHONPATH=os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src")))
    out = subprocess.run([sys.executable, "-c", PJIT_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert "PJIT_OK" in out.stdout, out.stdout + out.stderr


class TestSSGDMemoryPolicy:
    """make_ssgd_step(memory=...) threads the residual-memory policy into
    every node's DitherCtx exactly as the Trainer / make_train_step path
    does (PR: obs subsystem satellite)."""

    def _setup(self, key):
        model = _tiny_lm()
        params, _ = model.init(key)
        opt = OptConfig(name="sgd", lr=1e-2, grad_clip=None)
        batch = {
            "tokens": jax.random.randint(key, (4, 16), 0, model.cfg.vocab),
            "labels": jax.random.randint(key, (4, 16), 0, model.cfg.vocab),
        }
        return model, params, opt, batch

    def test_single_node_parity_with_train_step(self, key):
        """n_nodes=1 ssgd step == make_train_step, same memory policy."""
        from repro.launch.steps import make_train_step

        model, params, opt, batch = self._setup(key)
        pol = DitherPolicy(variant="paper", s=1.5)
        mem = "default=nsd"
        dcfg = SSGDConfig(n_nodes=1, s_schedule="fixed", s_base=1.5)

        ssgd_fn, _ = make_ssgd_step(model, opt, dcfg, pol, memory=mem)
        train_fn = jax.jit(make_train_step(model, opt, pol, memory=mem))

        bk = jax.random.fold_in(key, 7)
        st = init_opt_state(params, opt)
        p_a, _, m_a, _ = ssgd_fn(params, st, shard_batch(batch, 1), bk)
        st = init_opt_state(params, opt)
        p_b, _, m_b = train_fn(params, st, batch, bk)

        assert float(m_a["loss"]) == pytest.approx(float(m_b["loss"]),
                                                   rel=1e-6)
        for a, b in zip(jax.tree.leaves(p_a), jax.tree.leaves(p_b)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-7)

    def test_memory_policy_changes_backward(self, key):
        """An int8 residual codec must actually reach the backward pass:
        the step's gradients differ from the fp32-residual run."""
        model, params, opt, batch = self._setup(key)
        pol = DitherPolicy(variant="paper", s=1.5)
        dcfg = SSGDConfig(n_nodes=2, s_schedule="fixed", s_base=1.5)
        bk = jax.random.fold_in(key, 9)
        sb = shard_batch(batch, 2)

        fn_fp32, _ = make_ssgd_step(model, opt, dcfg, pol)
        fn_int8, _ = make_ssgd_step(model, opt, dcfg, pol,
                                    memory="default=int8")
        p_a, _, _, _ = fn_fp32(params, init_opt_state(params, opt), sb, bk)
        p_b, _, _, _ = fn_int8(params, init_opt_state(params, opt), sb, bk)
        diffs = [float(np.abs(np.asarray(a) - np.asarray(b)).max())
                 for a, b in zip(jax.tree.leaves(p_a), jax.tree.leaves(p_b))]
        assert max(diffs) > 0.0
