"""Mixture-of-Experts layer (dbrx-style 16e top-4, moonshot 64e top-6 + shared).

Two dispatch strategies:

* ``einsum`` — reference dense-dispatch with (T, E, C) one-hot masks. Exact,
  simple, O(T*E*C) memory: used for smoke tests / single-host examples and
  as the oracle the a2a path is tested against.
* ``a2a``   — production expert parallelism under ``jax.shard_map``: tokens
  are sharded over the data axes, experts over the "model" axis; dispatch is
  two ``all_to_all`` hops with fixed per-expert capacity (token dropping).
  This is the collective pattern real MoE systems (DeepSeek/Megablocks) use
  and is what the multi-pod dry-run exercises for the MoE archs.

Dithered backprop applies *inside* the expert FFN einsums (and the router),
so the paper's technique covers the dominant MoE FLOPs too.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import dense, dithered_einsum
from repro.core.policy import DitherCtx
from repro.models.layers import Init, Params, Specs, act_fn
from repro.parallel import axes as axlib


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_ff_expert: int
    n_shared: int = 0  # DeepSeek-style always-on shared experts
    capacity_factor: float = 1.25
    dispatch: str = "auto"  # auto | einsum | a2a
    aux_loss_coef: float = 0.01
    act: str = "swiglu"
    # int8-quantize the a2a payloads (absmax per shard, fwd AND bwd hops via
    # custom_vjp) — halves dispatch wire bytes; the paper's own "gradients
    # fit in 8 bits" observation applied to the token/grad traffic.
    a2a_int8: bool = False


def init_moe(key: jax.Array, d_model: int, cfg: MoEConfig, dtype
             ) -> Tuple[Params, Specs]:
    ini = Init(key, dtype)
    E, f = cfg.n_experts, cfg.d_ff_expert
    ini.normal("router", (d_model, E), ("embed", None), fan_in=d_model)
    ini.normal("w_gate", (E, d_model, f), ("expert", "embed", "expert_mlp"),
               fan_in=d_model)
    ini.normal("w_up", (E, d_model, f), ("expert", "embed", "expert_mlp"),
               fan_in=d_model)
    ini.normal("w_down", (E, f, d_model), ("expert", "expert_mlp", "embed"),
               fan_in=f)
    if cfg.n_shared:
        fs = cfg.d_ff_expert * cfg.n_shared
        ini.normal("ws_gate", (d_model, fs), ("embed", "mlp"), fan_in=d_model)
        ini.normal("ws_up", (d_model, fs), ("embed", "mlp"), fan_in=d_model)
        ini.normal("ws_down", (fs, d_model), ("mlp", "embed"), fan_in=fs)
    return ini.build()


def _routing(params, x2d, cfg: MoEConfig, ctx):
    """Router top-k: returns (choices (T,k), probs (T,k), aux_loss)."""
    logits = dense(x2d, params["router"], ctx=ctx, name="moe.router")
    logits = logits.astype(jnp.float32)
    probs_full = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs_full, cfg.top_k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)  # renormalize
    # switch-style load-balance aux loss
    T, E = logits.shape
    density = jnp.mean(
        jax.nn.one_hot(top_i[:, 0], E, dtype=jnp.float32), axis=0)
    density_proxy = jnp.mean(probs_full, axis=0)
    aux = jnp.sum(density * density_proxy) * E * cfg.aux_loss_coef
    return top_i, top_p, aux


def _expert_ffn(w_gate, w_up, w_down, xe, cfg: MoEConfig, ctx,
                name: str) -> jax.Array:
    """Batched per-expert FFN. xe: (E, C, d) -> (E, C, d)."""
    act = act_fn("silu" if cfg.act == "swiglu" else "gelu")
    g = dithered_einsum("ecd,edf->ecf", xe, w_gate, ctx=ctx, name=f"{name}.gate")
    u = dithered_einsum("ecd,edf->ecf", xe, w_up, ctx=ctx, name=f"{name}.up")
    h = act(g) * u
    return dithered_einsum("ecf,efd->ecd", h, w_down, ctx=ctx, name=f"{name}.down")


def _shared_ffn(params, x2d, cfg: MoEConfig, ctx, name: str) -> jax.Array:
    act = act_fn("silu" if cfg.act == "swiglu" else "gelu")
    g = dense(x2d, params["ws_gate"], ctx=ctx, name=f"{name}.sgate")
    u = dense(x2d, params["ws_up"], ctx=ctx, name=f"{name}.sup")
    return dense(act(g) * u, params["ws_down"], ctx=ctx, name=f"{name}.sdown")


# ---------------------------------------------------------------------------
# einsum (reference) dispatch
# ---------------------------------------------------------------------------

def _positions_in_expert(choices: jax.Array, n_experts: int) -> jax.Array:
    """For flattened choices (N,), position of each among same-expert picks."""
    onehot = jax.nn.one_hot(choices, n_experts, dtype=jnp.int32)  # (N, E)
    pos = jnp.cumsum(onehot, axis=0) * onehot  # 1-based at the picked column
    return jnp.sum(pos, axis=-1) - 1  # (N,)


def moe_einsum(params: Params, x2d: jax.Array, cfg: MoEConfig,
               ctx: Optional[DitherCtx], name: str = "moe"):
    T, d = x2d.shape
    E, k = cfg.n_experts, cfg.top_k
    cap = max(1, int(cfg.capacity_factor * T * k / E))
    top_i, top_p, aux = _routing(params, x2d, cfg, ctx)

    flat_choice = top_i.reshape(-1)  # (T*k,)
    pos = _positions_in_expert(flat_choice, E)  # (T*k,)
    keep = pos < cap
    disp = (
        jax.nn.one_hot(flat_choice, E, dtype=x2d.dtype)[:, :, None]
        * jax.nn.one_hot(jnp.where(keep, pos, cap), cap + 1,
                         dtype=x2d.dtype)[:, None, :-1]
    )  # (T*k, E, cap)
    disp = disp.reshape(T, k, E, cap)
    combine = disp * top_p.astype(x2d.dtype)[:, :, None, None]

    xe = jnp.einsum("tkec,td->ecd", disp, x2d)
    he = _expert_ffn(params["w_gate"], params["w_up"], params["w_down"],
                     xe, cfg, ctx, name)
    out = jnp.einsum("tkec,ecd->td", combine, he)
    if cfg.n_shared:
        out = out + _shared_ffn(params, x2d, cfg, ctx, name)
    return out, aux


# ---------------------------------------------------------------------------
# int8-on-the-wire all_to_all (both directions quantized via custom_vjp)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _int8_a2a(x: jax.Array, axis_name: str) -> jax.Array:
    return _int8_a2a_fwd(x, axis_name)[0]


def _quantized_hop(x: jax.Array, axis_name: str) -> jax.Array:
    """absmax-int8 the payload, a2a the int8 + tiny per-source scales."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)))
    scale = jnp.maximum(amax, jnp.finfo(jnp.float32).tiny) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127
                 ).astype(jnp.int8)
    q_recv = jax.lax.all_to_all(q, axis_name, split_axis=0, concat_axis=0,
                                tiled=False)
    ep = q.shape[0]
    scales = jnp.broadcast_to(scale, (ep, 1, 1, 1))
    scales_recv = jax.lax.all_to_all(scales, axis_name, split_axis=0,
                                     concat_axis=0, tiled=False)
    return (q_recv.astype(jnp.float32) * scales_recv).astype(x.dtype)


def _int8_a2a_fwd(x, axis_name):
    return _quantized_hop(x, axis_name), None


def _int8_a2a_bwd(axis_name, _, g):
    # transpose of a2a is a2a; the gradient hop is quantized too (the
    # paper's 8-bit-gradients claim applied to the wire)
    return (_quantized_hop(g, axis_name),)


_int8_a2a.defvjp(_int8_a2a_fwd, _int8_a2a_bwd)


def _dispatch_a2a(x: jax.Array, axis_name: str, int8_wire: bool) -> jax.Array:
    if int8_wire:
        return _int8_a2a(x, axis_name)
    return jax.lax.all_to_all(x, axis_name, split_axis=0, concat_axis=0,
                              tiled=False)


# ---------------------------------------------------------------------------
# all-to-all expert-parallel dispatch (shard_map)
# ---------------------------------------------------------------------------

def moe_a2a(params: Params, x2d: jax.Array, cfg: MoEConfig,
            ctx: Optional[DitherCtx], name: str = "moe"):
    """Tokens sharded over ALL mesh axes, experts over "model". Two a2a hops.

    Token rows must be split across the model axis too: with x replicated
    along "model", every expert column routes (and the experts then process)
    the SAME token population — a silent ep-fold redundancy. This was
    measured in the dry-run as a 16x FLOP bloat on dbrx (useful_ratio 0.043)
    and fixed in §Perf hillclimb iteration dbrx/It1.
    """
    rules = axlib.current_rules()
    assert rules is not None, "a2a dispatch needs sharding rules installed"
    mesh = rules.mesh
    ep_axis = "model"
    ep = mesh.shape[ep_axis]
    E = cfg.n_experts
    assert E % ep == 0, (E, ep)
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    token_axes = data_axes + (ep_axis,)

    key = ctx.key_for(name) if ctx is not None else jax.random.PRNGKey(0)
    policy = ctx.policy if ctx is not None else None
    program = ctx.program if ctx is not None else None
    # traced per-step policy state crosses the shard_map boundary as explicit
    # (replicated) inputs: the step for knob schedules, and the controller's
    # per-layer log-scales stacked into one vector (dict rebuilt inside from
    # the static name tuple) — closures over outer tracers are not portable
    # across shard_map implementations.
    step = (ctx.step if ctx is not None and ctx.step is not None
            else jnp.zeros((), jnp.int32))
    ctrl_names = tuple(sorted(ctx.ctrl)) if ctx is not None and ctx.ctrl else ()
    ctrl_vec = (jnp.stack([ctx.ctrl[n] for n in ctrl_names])
                if ctrl_names else jnp.zeros((0,), jnp.float32))

    def body(x_loc, router, w_gate_loc, w_up_loc, w_down_loc, key, step,
             ctrl_vec):
        # x_loc: (T_loc, d); w_*_loc: (E_loc, ...) — this device's experts
        T_loc, d = x_loc.shape
        E_loc = E // ep
        k = cfg.top_k
        cap = max(1, int(cfg.capacity_factor * T_loc * k / E))
        ctrl = ({n: ctrl_vec[i] for i, n in enumerate(ctrl_names)}
                if ctrl_names else None)
        inner_ctx = (DitherCtx(key=key, policy=policy, program=program,
                               step=step, ctrl=ctrl,
                               recorder=ctx.recorder if ctx else None,
                               memory=ctx.memory if ctx else None,
                               mem_recorder=(ctx.mem_recorder if ctx
                                             else None))
                     if policy is not None else None)

        top_i, top_p, aux = _routing({"router": router}, x_loc, cfg, inner_ctx)
        flat_choice = top_i.reshape(-1)  # (T_loc*k,)
        pos = _positions_in_expert(flat_choice, E)
        keep = pos < cap

        # scatter tokens into the (E, cap, d) send layout
        send = jnp.zeros((E, cap, d), x_loc.dtype)
        tok_idx = jnp.repeat(jnp.arange(T_loc), k)
        safe_e = jnp.where(keep, flat_choice, 0)
        safe_p = jnp.where(keep, pos, 0)
        vals = jnp.where(keep[:, None], x_loc[tok_idx], 0)
        send = send.at[safe_e, safe_p].add(vals)

        # a2a hop 1: (ep, E_loc, cap, d) -> gather my experts' tokens
        send = send.reshape(ep, E_loc, cap, d)
        recv = _dispatch_a2a(send, ep_axis, cfg.a2a_int8)
        # recv: (ep, E_loc, cap, d) = per-source tokens for my local experts
        xe = jnp.moveaxis(recv, 0, 1).reshape(E_loc, ep * cap, d)
        he = _expert_ffn(w_gate_loc, w_up_loc, w_down_loc, xe, cfg,
                         inner_ctx, name)
        # reverse a2a
        back = jnp.moveaxis(he.reshape(E_loc, ep, cap, d), 1, 0)
        got = _dispatch_a2a(back, ep_axis, cfg.a2a_int8)
        got = got.reshape(E, cap, d)

        # combine: gather each choice's output, weight by prob, mask dropped
        out_choice = got[safe_e, safe_p]
        out_choice = jnp.where(keep[:, None], out_choice, 0)
        out = jnp.sum(
            out_choice.reshape(T_loc, k, d)
            * top_p.astype(x_loc.dtype)[:, :, None],
            axis=1,
        )
        aux = jax.lax.pmean(aux, data_axes + (ep_axis,))
        return out, aux

    out, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(token_axes, None), P(None, None), P(ep_axis, None, None),
                  P(ep_axis, None, None), P(ep_axis, None, None), P(), P(),
                  P()),
        out_specs=(P(token_axes, None), P()),
        check_vma=False,
    )(x2d, params["router"], params["w_gate"], params["w_up"],
      params["w_down"], key, step, ctrl_vec)

    if cfg.n_shared:
        shared = _shared_ffn(params, x2d, cfg, ctx, name)
        out = out + shared
    return out, aux


def moe_layer(params: Params, x: jax.Array, cfg: MoEConfig,
              ctx: Optional[DitherCtx], name: str = "moe"):
    """x: (B, S, d) -> (y, aux_loss). Picks the dispatch strategy."""
    B, S, d = x.shape
    x2d = x.reshape(B * S, d)
    mode = cfg.dispatch
    if mode == "auto":
        rules = axlib.current_rules()
        ok = rules is not None and "model" in rules.mesh.shape \
            and cfg.n_experts % rules.mesh.shape["model"] == 0 \
            and rules.mesh.shape["model"] > 1
        if ok:
            # token rows must divide the full token-sharding extent
            # (decode steps with batch < n_devices fall back to einsum)
            n_tok_shards = 1
            for a in ("pod", "data", "model"):
                n_tok_shards *= rules.mesh.shape.get(a, 1)
            ok = (B * S) % n_tok_shards == 0
        mode = "a2a" if ok else "einsum"
    fn = moe_a2a if mode == "a2a" else moe_einsum
    out, aux = fn(params, x2d, cfg, ctx, name)
    return out.reshape(B, S, d), aux
