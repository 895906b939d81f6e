"""The plain reference against the program's model at a small size (f32)."""
import jax
import jax.numpy as jnp
import numpy as np

from chipbench.models import mamba2 as fam
from chipbench.reference import mamba2 as ref
from chipbench.tokens import TokenStream


def test_reference_matches_program_loss_and_grads(small_config):
    cfg = dict(small_config, dtype="float32", remat=False)
    model = fam.build(cfg)
    params = jax.jit(lambda k: fam.init(cfg, k))(jax.random.PRNGKey(0))
    batch = {k: jnp.asarray(v)
             for k, v in TokenStream(500, 2, 64, 3, 1.2)(0).items()}
    loss_p, grads_p = jax.value_and_grad(model.loss)(params, batch)
    with jax.default_matmul_precision("highest"):
        loss_r, grads_r = jax.value_and_grad(ref.loss)(params, batch, cfg)
    assert abs(float(loss_p) - float(loss_r)) < 1e-5
    for gp, gr in zip(jax.tree.leaves(grads_p), jax.tree.leaves(grads_r)):
        err = float(jnp.linalg.norm(gp - gr) / jnp.linalg.norm(gr))
        assert err < 1e-5


def test_weights_in_program_layout(small_config):
    model = fam.build(small_config)
    key = jax.random.PRNGKey(1)
    want = jax.eval_shape(lambda k: model.init(k)[0], key)
    got = jax.eval_shape(lambda k: fam.init(small_config, k), key)
    assert jax.tree.map(lambda s: (s.shape, s.dtype), want) == \
        jax.tree.map(lambda s: (s.shape, s.dtype), got)


def test_token_stream_seeded_and_rows_differ():
    a = TokenStream(500, 4, 64, 2**31 + 7, 1.2)
    b = TokenStream(500, 4, 64, 2**31 + 7, 1.2)
    x, y = a(0), b(0)
    np.testing.assert_array_equal(x["tokens"], y["tokens"])
    assert not np.array_equal(a(0)["tokens"], a(1)["tokens"])
    rows = {r.tobytes() for r in x["tokens"]}
    assert len(rows) == 4
    np.testing.assert_array_equal(x["tokens"][:, 1:], x["labels"][:, :-1])
    assert x["labels"].max() < 500


def test_nsd_is_unbiased_on_a_grid_of_delta():
    g = jnp.linspace(-3.0, 3.0, 64)
    keys = jax.random.split(jax.random.PRNGKey(0), 4000)
    q = jax.vmap(lambda k: ref.nsd(g, k, 0.5))(keys)
    np.testing.assert_allclose(jnp.round(q / 0.5), q / 0.5, atol=1e-6)
    np.testing.assert_allclose(jnp.mean(q, 0), g, atol=0.02)
    assert float(jnp.max(jnp.abs(q - g))) <= 0.5


def test_dithered_reference_matches_program_noise_scale(small_config):
    """Two dither draws, the program's and the reference's, give gradients
    of like norm."""
    from repro.core import DitherCtx, DitherPolicy

    cfg = dict(small_config, dtype="float32", remat=False, d_model=128,
               ssm_cfg=dict(small_config["ssm_cfg"], headdim=8))
    model = fam.build(cfg)
    params = jax.jit(lambda k: fam.init(cfg, k))(jax.random.PRNGKey(0))
    batch = {k: jnp.asarray(v)
             for k, v in TokenStream(500, 8, 512, 3, 1.2)(0).items()}
    ctx = DitherCtx.for_step(jax.random.PRNGKey(1), 0,
                             DitherPolicy(variant="paper", s=2.0))
    prog = jax.grad(lambda p: model.loss(p, batch, ctx=ctx))(params)
    with jax.default_matmul_precision("highest"):
        dith = jax.grad(lambda p: ref.loss(p, batch, cfg, dither_s=2.0,
                                           key=jax.random.PRNGKey(2)))(params)
    for name in (("embed", "table"), ("layers", "mixer", "in_proj")):
        p, d = (jnp.linalg.norm(t[name[0]][name[1]] if len(name) == 2
                                else t[name[0]][name[1]][name[2]])
                for t in (prog, dith))
        assert abs(float(p / d) - 1) < 0.1
