"""Plain float32 reference of Mamba-2 language-model training.

Independent of the program: it imports nothing of ``repro`` and reads its
sizes from the configuration file. Every contraction runs in float32 at
``Precision.HIGHEST`` (a TPU otherwise multiplies float32 in bf16 passes).
It follows Dao and Gu 2024 (arXiv:2405.21060), with these departures:

- The SSD layer is computed in its quadratic (attention-like) dual form over
  the whole sequence, one sequence at a time: y_t = sum_{s<=t} (C_t . B_s)
  exp(sum_{r=s+1..t} dt_r A) dt_s x_s. This is the same operator as the
  recurrence and as the program's chunked scan, with no chunks and no state
  passing. The decay exponent is a difference of two cumulative sums, which
  in float32 carries an absolute error of about 6e-8 times the sum's size
  (below 1e-3 at the sizes run), far under bf16 rounding.
- The norm epsilon is the configuration's ``norm_eps`` (see its file).
- Memory: each layer is recomputed in the backward pass (``jax.checkpoint``
  inside a scan over layers), the SSD runs one sequence at a time, and the
  tied LM head and its softmax run in blocks of rows, so 4 x 2048 tokens of
  the 48-layer model fit one 16 GB chip.

``contract`` is the one place where operands meet in a product; the control
passes a lower-precision one in its place.

Dithered backprop (Wiedemann et al. 2020, arXiv:2004.04729), where the
workload asks for it: the backward pass of every dense contraction (the in
and out projections and the tied LM head) multiplies the NSD-quantized
cotangent, g~ = Delta * clip(floor((g + nu) / Delta + 1/2), +-127) with
Delta = s * std(g) over the whole cotangent and nu ~ U(-Delta/2, Delta/2),
instead of g. The noise is the reference's own: independent per layer,
projection and step, and never the program's draw. The head's Delta is
worked out from the forward pass (the cotangent of the mean cross-entropy is
(p - y) / tokens, whose entries have mean 0), since the head runs in blocks.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
HEAD_ROWS = 1024  # rows of the LM head and softmax computed at a time


def exact(spec: str, a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.einsum(spec, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def _sizes(cfg: dict) -> dict:
    s = cfg["ssm_cfg"]
    d_inner = s["expand"] * cfg["d_model"]
    return {"d_inner": d_inner, "heads": d_inner // s["headdim"],
            "head_dim": s["headdim"], "d_state": s["d_state"],
            "groups": s["ngroups"], "d_conv": s["d_conv"]}


def nsd(g, key, delta):
    """Non-subtractive dithered quantization of ``g`` with step ``delta``."""
    nu = jax.random.uniform(key, g.shape, jnp.float32, -0.5, 0.5) * delta
    k = jnp.clip(jnp.floor((g + nu) / delta + 0.5), -127, 127)
    return jnp.where(delta > 0, k * delta, 0.0)


def dithered(contract, s):
    """``contract`` whose backward multiplies the NSD-quantized cotangent.

    Called as ``op(spec, a, b, key, delta)``; a ``delta`` of 0 or less means
    s * std of the cotangent.
    """

    @functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
    def op(spec, a, b, key, delta):
        return contract(spec, a, b)

    def fwd(spec, a, b, key, delta):
        return contract(spec, a, b), (a, b, key, delta)

    def bwd(spec, res, g):
        a, b, key, delta = res
        delta = jnp.where(delta > 0, delta, s * jnp.std(g))
        _, vjp = jax.vjp(lambda a, b: contract(spec, a, b), a, b)
        return (*vjp(nsd(g, key, delta)), None, jnp.zeros_like(delta))

    op.defvjp(fwd, bwd)
    return op


def dense_op(contract, dither_s, key):
    """The dense contraction: ``(spec, a, b, salt, delta=0)``."""
    if dither_s is None:
        return lambda spec, a, b, salt, delta=0.0: contract(spec, a, b)
    op = dithered(contract, dither_s)
    return lambda spec, a, b, salt, delta=0.0: op(
        spec, a, b, jax.random.fold_in(key, salt), jnp.float32(delta))


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def causal_conv(x, w, b):
    """Depthwise causal conv. x (B,T,C), w (K,C): y_t = b + sum_k w_k x_{t-K+1+k}."""
    K, T = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return b + sum(w[k] * xp[:, k:k + T] for k in range(K))


def ssd(x, dt, A, Bm, Cm, contract):
    """One sequence. x (T,H,P), dt (T,H), A (H,), Bm/Cm (T,G,N) -> (T,H,P)."""
    T, H, _ = x.shape
    rep = H // Bm.shape[1]
    cum = jnp.cumsum(dt * A, axis=0)  # (T,H)
    causal = jnp.tril(jnp.ones((T, T), bool))[:, :, None]
    decay = jnp.exp(jnp.where(causal, cum[:, None] - cum[None, :], -jnp.inf))
    cb = jnp.repeat(contract("tgn,sgn->tsg", Cm, Bm), rep, axis=2)
    return contract("tsh,shp->thp", cb * decay * dt[None], x)


def mixer(p, u, cfg, contract, dense):
    """Mamba-2 mixer. u (B,T,d_model) -> (B,T,d_model)."""
    z_ = _sizes(cfg)
    Bsz, T, _ = u.shape
    H, P, N, G, E = (z_["heads"], z_["head_dim"], z_["d_state"],
                     z_["groups"], z_["d_inner"])
    zxbcdt = dense("btd,de->bte", u, p["in_proj"], 0)
    z, xbc, dt = (zxbcdt[..., :E], zxbcdt[..., E:2 * E + 2 * G * N],
                  zxbcdt[..., 2 * E + 2 * G * N:])
    xbc = jax.nn.silu(causal_conv(xbc, p["conv_w"], p["conv_b"]))
    x = xbc[..., :E].reshape(Bsz, T, H, P)
    Bm = xbc[..., E:E + G * N].reshape(Bsz, T, G, N)
    Cm = xbc[..., E + G * N:].reshape(Bsz, T, G, N)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    # one sequence at a time, recomputed in the backward pass: the (T, T, H)
    # decay of all rows at once would not fit
    y = jax.lax.map(jax.checkpoint(
        lambda r: ssd(r[0], r[1], A, r[2], r[3], contract)), (x, dt, Bm, Cm))
    y = (y + p["D"][:, None] * x).reshape(Bsz, T, E)
    y = rms_norm(y * jax.nn.silu(z), p["norm"], cfg["norm_eps"])
    return dense("bte,ed->btd", y, p["out_proj"], 1)


def sq_cotangent(x, table, labels):
    """Per row t, sum over the vocabulary of (p_tv - y_tv)^2."""
    p = jax.nn.softmax(exact("td,vd->tv", x, table), -1)
    p_label = jnp.take_along_axis(p, labels[:, None], -1)[:, 0]
    return jnp.sum(p * p, -1) - 2 * p_label + 1


def loss(params, batch, cfg, contract=exact, dither_s=None, key=None):
    """Mean next-token cross-entropy over every token of the batch.

    With ``dither_s``, the dense contractions run dithered backprop with
    noise drawn from ``key``.
    """
    eps = cfg["norm_eps"]
    table = params["embed"]["table"]
    x = table[batch["tokens"]]
    n_layer = params["layers"]["ln"].shape[0]
    key = jax.random.PRNGKey(0) if key is None else key

    @jax.checkpoint
    def layer(x, pl):
        p, i = pl
        dense = dense_op(contract, dither_s, jax.random.fold_in(key, i))
        return x + mixer(p["mixer"], rms_norm(x, p["ln"], eps), cfg,
                         contract, dense), None

    x, _ = jax.lax.scan(layer, x, (params["layers"], jnp.arange(n_layer)))
    x = rms_norm(x, params["head"]["ln_f"], eps)
    block = math.gcd(HEAD_ROWS, batch["labels"].size)
    rows = x.reshape(-1, block, x.shape[-1])
    labels = batch["labels"].reshape(-1, block)
    n = labels.size
    head = dense_op(contract, dither_s, jax.random.fold_in(key, n_layer))
    delta = 0.0
    if dither_s is not None:
        # std over all (tokens x vocab) entries of (p - y) / n, mean 0
        fixed = jax.lax.stop_gradient((rows, table))
        r = jax.lax.map(lambda xl: sq_cotangent(xl[0], fixed[1], xl[1]),
                        (fixed[0], labels))
        delta = dither_s * jnp.sqrt(jnp.sum(r) / (n * table.shape[0])) / n

    @jax.checkpoint
    def head_block(total, xl):
        xr, lr, i = xl
        logits = head("td,vd->tv", xr, table, i, delta)
        nll = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
            logits, lr[:, None], -1)[:, 0]
        return total + jnp.sum(nll), None

    total, _ = jax.lax.scan(head_block, jnp.zeros((), jnp.float32),
                            (rows, labels, jnp.arange(rows.shape[0])))
    return total / n


def lr_at(opt: dict, step: int) -> float:
    """Linear warm-up from lr / warmup, then cosine down to min_lr_ratio."""
    warm = min(1.0, (step + 1) / max(opt["warmup_steps"], 1))
    t = min(max((step - opt["warmup_steps"]) /
                max(opt["total_steps"] - opt["warmup_steps"], 1), 0.0), 1.0)
    r = opt["min_lr_ratio"]
    return opt["lr"] * warm * (r + (1 - r) * 0.5 * (1 + math.cos(math.pi * t)))


def adamw(params, grads, m, v, step, opt: dict, lr):
    """AdamW with the gradient clipped to a global norm of ``grad_clip``."""
    leaves = jax.tree.leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in leaves))
    scale = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gnorm, 1e-12))
    grads = jax.tree.map(lambda g: g * scale, grads)
    b1, b2, t = opt["b1"], opt["b2"], jnp.asarray(step + 1, jnp.float32)
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, grads)

    def upd(w, m, v):
        mhat, vhat = m / (1 - b1 ** t), v / (1 - b2 ** t)
        return w - lr * (mhat / (jnp.sqrt(vhat) + opt["eps"])
                         + opt["weight_decay"] * w)

    return jax.tree.map(upd, params, m, v), grads, m, v


def leaf_norms(tree) -> list:
    return [jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
            for a in jax.tree.leaves(tree)]


def train_readings(params0, batches, cfg: dict, opt: dict, contract=exact,
                   dither_s=None, key=None) -> dict:
    """Follow the program's first ``len(batches)`` AdamW steps.

    Returns each step's loss, the per-leaf norms of the first gradient as
    the optimizer takes it (after clipping), and the per-leaf norms of the
    parameters' change over all the steps. With ``dither_s``, the backward
    pass is dithered with noise drawn from ``key``.
    """
    key = jax.random.PRNGKey(0) if key is None else key

    # the key is an argument, not a constant of the program, so that every
    # seed is served by one compiled step
    def step(params, m, v, batch, i, lr, key):
        with jax.default_matmul_precision("highest"):
            value, grads = jax.value_and_grad(loss)(
                params, batch, cfg, contract, dither_s,
                jax.random.fold_in(key, i.astype(jnp.int32)))
            params, grads, m, v = adamw(params, grads, m, v, i, opt, lr)
        return params, m, v, value, leaf_norms(grads)

    step = jax.jit(step, donate_argnums=(0, 1, 2))
    params = jax.jit(lambda p: jax.tree.map(
        lambda a: a.astype(jnp.float32), p))(params0)
    zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p))
    m, v = zeros(params), zeros(params)
    losses, grad_norms = [], None
    for i, batch in enumerate(batches):
        params, m, v, value, gn = step(params, m, v, batch, jnp.float32(i),
                                       jnp.float32(lr_at(opt, i)), key)
        losses.append(float(value))
        if grad_norms is None:
            grad_norms = [float(g) for g in gn]
    change = jax.jit(lambda a, b: leaf_norms(jax.tree.map(
        lambda x, y: x - y.astype(jnp.float32), a, b)))(params, params0)
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": [float(c) for c in change]}
