"""Mamba-2 SSD: the chunked algorithm vs a naive per-step recurrence oracle,
and decode-vs-train consistency."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import mamba as M


def _naive_ssd(x, dt, A, Bm, Cm):
    """Direct recurrence: h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t^T."""
    Bsz, S, H, Pd = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    rep = H // G
    Bh = jnp.repeat(Bm, rep, axis=2)
    Ch = jnp.repeat(Cm, rep, axis=2)
    h = jnp.zeros((Bsz, H, N, Pd))
    ys = []
    for t in range(S):
        decay = jnp.exp(dt[:, t] * A)  # (B,H)
        h = h * decay[:, :, None, None] + jnp.einsum(
            "bh,bhn,bhp->bhnp", dt[:, t], Bh[:, t], x[:, t])
        ys.append(jnp.einsum("bhn,bhnp->bhp", Ch[:, t], h))
    return jnp.stack(ys, axis=1), h


@pytest.mark.parametrize("chunk", [4, 8, 16])
def test_chunked_ssd_matches_recurrence(key, chunk):
    Bsz, S, H, Pd, G, N = 2, 16, 4, 8, 2, 6
    cfg = M.SSMConfig(d_model=32, d_inner=H * Pd, head_dim=Pd, d_state=N,
                      n_groups=G, chunk=chunk)
    ks = jax.random.split(key, 4)
    x = jax.random.normal(ks[0], (Bsz, S, H, Pd))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (Bsz, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (Bsz, S, G, N)) * 0.5
    Cm = jax.random.normal(jax.random.fold_in(key, 9), (Bsz, S, G, N)) * 0.5
    y_chunk, h_chunk = M._ssd_chunked(x, dt, A, Bm, Cm, cfg)
    y_naive, h_naive = _naive_ssd(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y_chunk), np.asarray(y_naive),
                               rtol=1e-4, atol=1e-4)
    # h_final layout (B,H,N,P)
    np.testing.assert_allclose(np.asarray(h_chunk), np.asarray(h_naive),
                               rtol=1e-4, atol=1e-4)


def test_mixer_decode_matches_train(key):
    """Feeding a sequence token-by-token through the decode step must
    reproduce the train-mode mixer outputs."""
    cfg = M.SSMConfig(d_model=16, d_inner=32, head_dim=8, d_state=6,
                      n_groups=1, chunk=4)
    params, _ = M.init_mamba_mixer(key, cfg, jnp.float32)
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 8, 16)) * 0.5
    y_train = M.mamba_mixer(params, x, cfg)
    cache = M.MambaCache.init(cfg, 2, jnp.float32)
    ys = []
    for t in range(8):
        y, cache = M.mamba_decode_step(params, x[:, t:t + 1], cache, cfg)
        ys.append(y)
    y_decode = jnp.concatenate(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_decode), np.asarray(y_train),
                               rtol=2e-3, atol=2e-3)


def test_full_ssm_lm_decode_matches_forward(key):
    cfg = M.SSMLMConfig(
        name="t", n_layers=2, vocab=64,
        ssm=M.SSMConfig(d_model=16, d_inner=32, head_dim=8, d_state=6,
                        chunk=4),
        dtype=jnp.float32, remat=False)
    params, _ = M.init_ssm_lm(key, cfg)
    toks = jax.random.randint(jax.random.fold_in(key, 1), (1, 8), 0, 64)
    logits_train, _ = M.forward(params, cfg, toks)
    cache = M.init_cache(cfg, 1, 8)
    outs = []
    for t in range(8):
        lg, cache = M.decode_step(params, cfg, cache, toks[:, t:t + 1],
                                  jnp.asarray(t))
        outs.append(lg)
    logits_dec = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(logits_dec),
                               np.asarray(logits_train), rtol=2e-3,
                               atol=2e-3)


def test_chunked_ssd_non_divisible_seq(key):
    """Seq not divisible by chunk (e.g. hymba's +meta_tokens prefill) must
    pad exactly — regression for the 32896 % 256 != 0 dry-run failure."""
    cfg = M.SSMConfig(d_model=32, d_inner=32, head_dim=8, d_state=6,
                      n_groups=2, chunk=8)
    ks = jax.random.split(key, 5)
    Bsz, S, H, Pd, G, N = 2, 13, 4, 8, 2, 6
    x = jax.random.normal(ks[0], (Bsz, S, H, Pd))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (Bsz, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (Bsz, S, G, N)) * 0.5
    Cm = jax.random.normal(ks[4], (Bsz, S, G, N)) * 0.5
    y_c, h_c = M._ssd_chunked(x, dt, A, Bm, Cm, cfg)
    y_n, h_n = _naive_ssd(x, dt, A, Bm, Cm)
    assert y_c.shape == (Bsz, S, H, Pd)
    np.testing.assert_allclose(np.asarray(y_c), np.asarray(y_n), rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(h_c), np.asarray(h_n), rtol=1e-4,
                               atol=1e-4)


def test_chunked_ssd_grads_finite_at_long_chunk(key):
    """Within a 64-step chunk the decay sum dt * A passes 88, where the
    masked upper triangle's exp(cum_i - cum_j) overflows f32. Gradients
    must stay finite (the mamba2-370m config uses 256-step chunks)."""
    Bsz, S, H, Pd, G, N = 1, 64, 4, 8, 1, 6
    cfg = M.SSMConfig(d_model=32, d_inner=H * Pd, head_dim=Pd, d_state=N,
                      n_groups=G, chunk=64)
    ks = jax.random.split(key, 4)
    x = jax.random.normal(ks[0], (Bsz, S, H, Pd))
    dt = jnp.full((Bsz, S, H), 0.1)
    A = -jnp.full((H,), 16.0)  # sum over the chunk: -102
    Bm = jax.random.normal(ks[2], (Bsz, S, G, N)) * 0.5
    Cm = jax.random.normal(ks[3], (Bsz, S, G, N)) * 0.5
    grads = jax.grad(lambda *a: jnp.sum(M._ssd_chunked(*a, cfg)[0] ** 2),
                     argnums=(0, 1, 2, 3, 4))(x, dt, A, Bm, Cm)
    for g in grads:
        assert bool(jnp.all(jnp.isfinite(g)))
    y_chunk, _ = M._ssd_chunked(x, dt, A, Bm, Cm, cfg)
    y_naive, _ = _naive_ssd(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y_chunk), np.asarray(y_naive),
                               rtol=1e-4, atol=1e-4)
