"""Mamba-2 (state-space duality / SSD), after Dao & Gu 2024 (arXiv:2405.21060).

Chunked SSD for training/prefill (within-chunk quadratic term + cross-chunk
state recurrence), O(1)-state single-token decode for serving — this is the
sub-quadratic family that carries the ``long_500k`` shape cells.

Dithered backprop covers the in/out projections (the FLOP-dominant dense
matmuls). The state recurrence itself is elementwise and stays exact — see
DESIGN.md §5 (mamba2 row).

The training forward names its parts with ``repro.obs.trace.annotate``
scopes, so a device profile attributes each op (forward, remat recompute
and backward alike): ``embed``, ``layers`` (the scan over the blocks),
``block/norm`` (pre-mixer norm and the residual add), ``mixer/in_proj``,
``mixer/conv``, ``mixer/ssd`` (chunked scan, ``D`` skip, gated norm),
``mixer/out_proj`` and ``head`` (final norm, unembedding, log-softmax and
NLL).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dense
from repro.core.policy import DitherCtx
from repro.core.probe import tap
from repro.models import layers as L
from repro.obs.trace import annotate
from repro.parallel.axes import shard_act


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    d_model: int
    d_inner: int  # expand * d_model
    head_dim: int  # P
    d_state: int  # N
    n_groups: int = 1
    d_conv: int = 4
    chunk: int = 256
    dt_min: float = 0.001
    dt_max: float = 0.1
    # dtype of the intra-chunk (quadratic) einsum OPERANDS; accumulation is
    # always f32 (preferred_element_type). "bf16" halves the bytes of the
    # (B,nc,Q,Q,H) score/decay tensors — §Perf mamba2/It1.
    intra_dtype: str = "f32"

    @property
    def n_heads(self) -> int:
        return self.d_inner // self.head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.d_state

    @property
    def d_in_proj(self) -> int:
        return 2 * self.d_inner + 2 * self.n_groups * self.d_state + self.n_heads


def init_mamba_mixer(key: jax.Array, cfg: SSMConfig, dtype) -> Tuple[L.Params, L.Specs]:
    ini = L.Init(key, dtype)
    ini.normal("in_proj", (cfg.d_model, cfg.d_in_proj), ("embed", "ssm_inner"),
               fan_in=cfg.d_model)
    ini.normal("conv_w", (cfg.d_conv, cfg.conv_dim), (None, "ssm_inner"),
               stddev=1.0 / np.sqrt(cfg.d_conv))
    ini.zeros("conv_b", (cfg.conv_dim,), ("ssm_inner",))
    # A in (-exp) parameterization; dt bias set for softplus(dt) in [dt_min, dt_max]
    a_init = jnp.log(jnp.linspace(1.0, 16.0, cfg.n_heads))
    ini.const("A_log", a_init, (None,))
    dt = jnp.exp(jax.random.uniform(ini.next_key(), (cfg.n_heads,)) *
                 (np.log(cfg.dt_max) - np.log(cfg.dt_min)) + np.log(cfg.dt_min))
    ini.const("dt_bias", dt + jnp.log(-jnp.expm1(-dt)), (None,))
    ini.zeros("D", (cfg.n_heads,), (None,))
    ini.ones("norm", (cfg.d_inner,), ("ssm_inner",))
    ini.normal("out_proj", (cfg.d_inner, cfg.d_model), ("ssm_inner", "embed"),
               fan_in=cfg.d_inner)
    return ini.build()


def _causal_conv(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv. x (B,S,C), w (K,C) -> (B,S,C)."""
    K, C = w.shape
    y = jax.lax.conv_general_dilated(
        x, w[:, None, :],  # (K, 1, C) HIO with feature groups
        window_strides=(1,), padding=[(K - 1, 0)],
        dimension_numbers=("NHC", "HIO", "NHC"),
        feature_group_count=C,
    )
    return y + b


def _ssd_chunked(x, dt, A, Bm, Cm, cfg: SSMConfig,
                 h0: Optional[jax.Array] = None):
    """Chunked SSD scan.

    x: (B,S,H,P) dt: (B,S,H) A: (H,) Bm/Cm: (B,S,G,N)
    Returns (y (B,S,H,P), h_final (B,H,P,N)).
    """
    Bsz, S, H, Pd = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    Q = min(cfg.chunk, S)
    S_orig = S
    if S % Q != 0:
        # pad the tail: dt=0 there => decay=1 and zero state contribution,
        # so earlier (causal) outputs are exact; padded outputs are sliced off
        pad = Q - S % Q
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        S = S + pad
    nc = S // Q
    rep = H // G

    # heads are kept factored as (G, rep) — B/C are NEVER repeated to H
    # (repeating them 32x was measured as a pure bytes/FLOP tax, §Perf
    # mamba2/It4): the group dim broadcasts inside the einsums instead.
    xc = x.reshape(Bsz, nc, Q, G, rep, Pd)
    dtc = dt.reshape(Bsz, nc, Q, G, rep)
    Bg = Bm.reshape(Bsz, nc, Q, G, N)
    Cg = Cm.reshape(Bsz, nc, Q, G, N)

    dA = dtc * A.reshape(G, rep)  # (B,nc,Q,G,rep), negative
    cum = jnp.cumsum(dA, axis=2)  # within-chunk cumulative log-decay

    # ---- intra-chunk (quadratic in Q) ----
    # L[i,j] = exp(cum_i - cum_j) for i >= j (exp/cumsum stay f32; only the
    # matmul OPERANDS drop to intra_dtype, accumulating in f32). The mask
    # goes INSIDE the exp: above the diagonal cum_i - cum_j > 0 grows with
    # the chunk (past 88 at Q=256) and exp overflows to inf, whose
    # gradient through a where-after-exp is 0 * inf = NaN.
    op_dtype = jnp.bfloat16 if cfg.intra_dtype == "bf16" else jnp.float32
    diff = cum[:, :, :, None] - cum[:, :, None, :, :, :]  # (B,nc,Q,Q,G,rep)
    tri = jnp.tril(jnp.ones((Q, Q), bool))
    Lmat = jnp.exp(jnp.where(tri[None, None, :, :, None, None], diff,
                             -jnp.inf))
    # scores are per-GROUP (shared by rep heads): 1/rep of the naive FLOPs
    scores = jnp.einsum("bcign,bcjgn->bcijg", Cg.astype(op_dtype),
                        Bg.astype(op_dtype),
                        preferred_element_type=jnp.float32)
    M = scores[..., None] * Lmat * dtc[:, :, None, :, :, :]
    y_intra = jnp.einsum("bcijgr,bcjgrp->bcigrp", M.astype(op_dtype),
                         xc.astype(op_dtype),
                         preferred_element_type=jnp.float32)

    # ---- chunk states ----
    decay_to_end = jnp.exp(cum[:, :, -1:] - cum)  # (B,nc,Q,G,rep)
    states = jnp.einsum(
        "bcjgr,bcjgn,bcjgrp->bcgrnp",
        (decay_to_end * dtc).astype(jnp.float32),
        Bg.astype(jnp.float32), xc.astype(jnp.float32))

    # ---- cross-chunk recurrence over nc (sequential scan, nc is small) ----
    chunk_decay = jnp.exp(jnp.sum(dA, axis=2))  # (B,nc,G,rep)

    def scan_fn(h, inp):
        st, dec = inp  # (B,G,rep,N,P), (B,G,rep)
        h_new = h * dec[:, :, :, None, None] + st
        return h_new, h

    if h0 is None:
        h0 = jnp.zeros((Bsz, G, rep, N, Pd), jnp.float32)
    else:
        h0 = h0.reshape(Bsz, G, rep, N, Pd)
    h_final, h_prev = jax.lax.scan(
        scan_fn, h0,
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    h_prev = jnp.moveaxis(h_prev, 0, 1)  # (B,nc,G,rep,N,P)

    # ---- inter-chunk contribution ----
    # C stays grouped; the per-head decay scales the OUTPUT (P-sized), not a
    # repeated (N-sized) C tensor
    y_inter = jnp.einsum(
        "bcign,bcgrnp->bcigrp", Cg.astype(op_dtype),
        h_prev.astype(op_dtype), preferred_element_type=jnp.float32)
    y_inter = y_inter * jnp.exp(cum)[..., None]

    y = (y_intra + y_inter).reshape(Bsz, S, H, Pd)
    return y[:, :S_orig], h_final.reshape(Bsz, H, N, Pd)


def mamba_mixer(params: L.Params, x: jax.Array, cfg: SSMConfig, *,
                ctx: Optional[DitherCtx] = None, name: str = "ssm",
                taps=None) -> jax.Array:
    """Full Mamba-2 mixer for train/prefill. x: (B,S,d_model)."""
    with annotate("mixer"):
        return _mixer(params, x, cfg, ctx=ctx, name=name, taps=taps)


def _mixer(params, x, cfg: SSMConfig, *, ctx, name, taps):
    B, S, _ = x.shape
    H, Pd, G, N = cfg.n_heads, cfg.head_dim, cfg.n_groups, cfg.d_state
    with annotate("in_proj"):
        zxbcdt = dense(x, params["in_proj"], ctx=ctx, name=f"{name}.in")
    zxbcdt = tap(zxbcdt, taps, f"{name}.in_out")
    z, xs, Bm, Cm, dt = jnp.split(
        zxbcdt,
        [cfg.d_inner, 2 * cfg.d_inner, 2 * cfg.d_inner + G * N,
         2 * cfg.d_inner + 2 * G * N],
        axis=-1,
    )
    conv_in = jnp.concatenate([xs, Bm, Cm], axis=-1)
    with annotate("conv"):
        conv_out = jax.nn.silu(
            _causal_conv(conv_in, params["conv_w"], params["conv_b"]))
    xs, Bm, Cm = jnp.split(
        conv_out, [cfg.d_inner, cfg.d_inner + G * N], axis=-1)
    xs = xs.reshape(B, S, H, Pd)
    Bm = Bm.reshape(B, S, G, N)
    Cm = Cm.reshape(B, S, G, N)
    A = -jnp.exp(params["A_log"].astype(jnp.float32))
    dt = jax.nn.softplus(dt.astype(jnp.float32) +
                         params["dt_bias"].astype(jnp.float32))
    with annotate("ssd"):
        y, _ = _ssd_chunked(xs, dt, A, Bm, Cm, cfg)
        y = y + params["D"].astype(jnp.float32)[None, None, :, None] * \
            xs.astype(jnp.float32)
        y = y.reshape(B, S, cfg.d_inner).astype(x.dtype)
        y = L.rms_norm(y * jax.nn.silu(z), params["norm"])
    y = shard_act(y, ("batch", "seq", "act_ssm_inner"))
    with annotate("out_proj"):
        return dense(y, params["out_proj"], ctx=ctx, name=f"{name}.out")


class MambaCache:
    """Decode cache = {"conv": window, "state": SSM state} (dict keys make
    the leaves identifiable for sharding-rule assignment in the dry-run)."""

    @staticmethod
    def init(cfg: SSMConfig, batch: int, dtype) -> Dict[str, jax.Array]:
        return {
            "conv": jnp.zeros((batch, cfg.d_conv - 1, cfg.conv_dim), dtype),
            "state": jnp.zeros(
                (batch, cfg.n_heads, cfg.d_state, cfg.head_dim), jnp.float32),
        }

    @staticmethod
    def specs(cfg: SSMConfig, batch: int, dtype):
        return {
            "conv": jax.ShapeDtypeStruct(
                (batch, cfg.d_conv - 1, cfg.conv_dim), dtype),
            "state": jax.ShapeDtypeStruct(
                (batch, cfg.n_heads, cfg.d_state, cfg.head_dim), jnp.float32),
        }


def mamba_decode_step(params: L.Params, x: jax.Array, cache, cfg: SSMConfig,
                      *, name: str = "ssm"):
    """One token. x: (B,1,d_model). Returns (y (B,1,d), new_cache)."""
    B = x.shape[0]
    H, Pd, G, N = cfg.n_heads, cfg.head_dim, cfg.n_groups, cfg.d_state
    conv_state, h = cache["conv"], cache["state"]
    zxbcdt = dense(x[:, 0], params["in_proj"], name=f"{name}.in")
    z, xs, Bm, Cm, dt = jnp.split(
        zxbcdt,
        [cfg.d_inner, 2 * cfg.d_inner, 2 * cfg.d_inner + G * N,
         2 * cfg.d_inner + 2 * G * N],
        axis=-1,
    )
    conv_in = jnp.concatenate([xs, Bm, Cm], axis=-1)  # (B, conv_dim)
    window = jnp.concatenate([conv_state, conv_in[:, None, :]], axis=1)
    conv_out = jnp.einsum("bkc,kc->bc", window.astype(jnp.float32),
                          params["conv_w"].astype(jnp.float32))
    conv_out = jax.nn.silu(conv_out + params["conv_b"].astype(jnp.float32))
    new_conv_state = window[:, 1:, :].astype(conv_state.dtype)

    xs, Bm, Cm = jnp.split(conv_out, [cfg.d_inner, cfg.d_inner + G * N],
                           axis=-1)
    xs = xs.reshape(B, H, Pd)
    Bm = jnp.repeat(Bm.reshape(B, G, N), H // G, axis=1)  # (B,H,N)
    Cm = jnp.repeat(Cm.reshape(B, G, N), H // G, axis=1)
    A = -jnp.exp(params["A_log"].astype(jnp.float32))
    dt = jax.nn.softplus(dt.astype(jnp.float32) +
                         params["dt_bias"].astype(jnp.float32))  # (B,H)
    decay = jnp.exp(dt * A)  # (B,H)
    h_new = h * decay[:, :, None, None] + jnp.einsum(
        "bh,bhn,bhp->bhnp", dt, Bm, xs.astype(jnp.float32))
    y = jnp.einsum("bhn,bhnp->bhp", Cm, h_new)
    y = y + params["D"].astype(jnp.float32)[None, :, None] * xs.astype(jnp.float32)
    y = y.reshape(B, cfg.d_inner).astype(x.dtype)
    y = L.rms_norm(y * jax.nn.silu(z), params["norm"])
    y = dense(y, params["out_proj"], name=f"{name}.out")
    return y[:, None, :], {"conv": new_conv_state, "state": h_new}


# ---------------------------------------------------------------------------
# full SSM language model (mamba2-370m)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SSMLMConfig:
    name: str
    n_layers: int
    vocab: int
    ssm: SSMConfig
    dtype: Any = jnp.bfloat16
    tie_embeddings: bool = True
    remat: bool = True
    scan_unroll: bool = False

    @property
    def d_model(self) -> int:
        return self.ssm.d_model

    @property
    def param_count(self) -> int:
        c = self.ssm
        per_layer = (c.d_model * c.d_in_proj + c.d_conv * c.conv_dim +
                     c.d_inner * c.d_model + 3 * c.n_heads + 2 * c.d_inner +
                     c.d_model)
        emb = self.vocab * c.d_model * (1 if self.tie_embeddings else 2)
        return self.n_layers * per_layer + emb

    @property
    def active_param_count(self) -> int:
        return self.param_count


def init_ssm_lm(key: jax.Array, cfg: SSMLMConfig) -> Tuple[L.Params, L.Specs]:
    keys = jax.random.split(key, cfg.n_layers + 2)
    emb_p, emb_s = L.init_embedding(keys[0], cfg.vocab, cfg.d_model, cfg.dtype)
    blocks = []
    for i in range(cfg.n_layers):
        ini = L.Init(keys[1 + i], cfg.dtype)
        mix_p, mix_s = init_mamba_mixer(ini.next_key(), cfg.ssm, cfg.dtype)
        sub = L.Init(jax.random.PRNGKey(0), cfg.dtype)
        sub.params, sub.specs = mix_p, mix_s
        ini.sub("mixer", sub)
        ini.ones("ln", (cfg.d_model,), (None,))
        blocks.append(ini.build())
    stacked_p, stacked_s = L.stack_layers(blocks)
    ini = L.Init(keys[-1], cfg.dtype)
    ini.ones("ln_f", (cfg.d_model,), (None,))
    head_p, head_s = ini.build()
    return ({"embed": emb_p, "layers": stacked_p, "head": head_p},
            {"embed": emb_s, "layers": stacked_s, "head": head_s})


def _block(x, p, cfg: SSMLMConfig, *, ctx, name, taps=None):
    """One residual block: pre-mixer norm, mixer, residual add."""
    with annotate("block/norm"):
        h = L.rms_norm(x, p["ln"])
    y = mamba_mixer(p["mixer"], h, cfg.ssm, ctx=ctx, name=name, taps=taps)
    with annotate("block/norm"):
        return x + y


def forward(params, cfg: SSMLMConfig, tokens: jax.Array, *,
            ctx: Optional[DitherCtx] = None, taps=None):
    with annotate("embed"):
        x = L.embed(params["embed"], tokens)

    if taps is not None:
        for i in range(cfg.n_layers):
            p = L.layer_slice(params["layers"], i)
            x = _block(x, p, cfg, ctx=ctx, name=f"L{i}.ssm", taps=taps)
    else:
        def body(x, p):
            return _block(x, p, cfg, ctx=ctx, name="L.ssm"), None

        f = body
        if cfg.remat:
            f = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
        # the scan's own ops (stacked-gradient updates, the layout copies
        # XLA inserts without a name stack) take the loop's name
        with annotate("layers"):
            x, _ = jax.lax.scan(f, x, params["layers"],
                                unroll=cfg.n_layers if cfg.scan_unroll else 1)

    with annotate("head"):
        x = L.rms_norm(x, params["head"]["ln_f"])
        logits = L.unembed(params["embed"], x, ctx=ctx)
    return logits, jnp.zeros((), jnp.float32)


def loss_fn(params, cfg: SSMLMConfig, batch, *, ctx=None, taps=None):
    logits, _ = forward(params, cfg, batch["tokens"], ctx=ctx, taps=taps)
    with annotate("head"):
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, batch["labels"][..., None],
                                   axis=-1)[..., 0]
        return jnp.mean(nll)


def init_cache(cfg: SSMLMConfig, batch: int, max_len: int, dtype=None):
    del max_len  # O(1) state
    dtype = dtype or cfg.dtype
    return [MambaCache.init(cfg.ssm, batch, dtype)
            for _ in range(cfg.n_layers)]


def cache_specs(cfg: SSMLMConfig, batch: int, max_len: int, dtype=None):
    del max_len
    dtype = dtype or cfg.dtype
    return [MambaCache.specs(cfg.ssm, batch, dtype)
            for _ in range(cfg.n_layers)]


def decode_step(params, cfg: SSMLMConfig, cache, token: jax.Array,
                t: jax.Array, *, ctx=None):
    del t  # stateful: position-free
    x = L.embed(params["embed"], token)
    new_cache = []
    for i in range(cfg.n_layers):
        p = L.layer_slice(params["layers"], i)
        h = L.rms_norm(x, p["ln"])
        y, kv = mamba_decode_step(p["mixer"], h, cache[i], cfg.ssm,
                                  name=f"L{i}.ssm")
        x = x + y
        new_cache.append(kv)
    x = L.rms_norm(x, params["head"]["ln_f"])
    logits = L.unembed(params["embed"], x)
    return logits, new_cache


def prefill(params, cfg: SSMLMConfig, tokens: jax.Array, max_len: int):
    """Token-by-token prompt scan through the decode state.

    tokens (B, S) -> (logits (B, S, V), cache, t = S - 1). The decode
    recurrence IS the model here (no separate bulk path is needed for
    correctness — the chunked SSD forward is a training-time optimization),
    so prefill scans ``decode_step`` to keep serving numerics identical to
    the decode loop that follows.
    """
    B, S = tokens.shape
    cache = init_cache(cfg, B, max_len)

    def body(c, tok):
        logits, c = decode_step(params, cfg, c, tok[:, None],
                                jnp.zeros((), jnp.int32))
        return c, logits[:, 0]

    cache, logits_seq = jax.lax.scan(body, cache, tokens.T)
    return (jnp.moveaxis(logits_seq, 0, 1), cache,
            jnp.asarray(S - 1, jnp.int32))
