"""Dithered backprop as composable JAX ops (the paper's eqs. 7-9).

Every weight-bearing contraction in the framework goes through ``dense`` /
``conv2d`` / ``dithered_einsum`` below. Forward is exact; the backward pass
intercepts the pre-activation cotangent ``g`` (= delta_z in the paper),
applies the resolved quantizer once, and reuses the quantized tensor for
BOTH backward products:

    delta_a = g~ . W^T        (activation gradient, eq. 8)
    delta_W = a^T . g~        (weight gradient,     eq. 9)

Bias gradients (a cheap reduction, not a matmul) use the exact cotangent.

Policy resolution is per layer name (``ctx.resolve(name)`` — rules, knob
schedules and the sparsity controller live in ``repro.core.schedule``). The
resolved result splits static from traced state:

* ``StaticSpec`` (variant / telemetry / residual mode) is the custom_vjp's
  static argument;
* the numeric knobs ``[s, meprop_k_frac, row_alpha]`` arrive as a traced f32
  ``(3,)`` array, so a schedule that changes ``s`` every step re-uses the
  compiled backward — zero recompiles (pinned by tests/test_schedule.py).

Residual memory (``repro.memory``): the forward residual each op saves for
its backward — the activation ``x`` that the weight-gradient product
consumes — goes through the layer's resolved residual codec
(``spec.residual``): ``fwd`` stores ``codec.encode(x)`` instead of dense
fp32 and ``bwd`` decodes, so between the forward and backward passes only
the compressed form stays live. ``dx = g~ . W^T`` never touches ``x`` and
is bit-identical to the dense-residual path; only ``dW = x^T . g~`` sees
the (unbiased for nsd, scale/2-bounded for int8) reconstruction. Mode
``"remat"`` instead wraps the op in ``jax.checkpoint`` — the VJP
recomputes the forward from the op inputs rather than decoding. The codec
choice is static per layer; knob schedules still recompile nothing
(compile-counter pins in tests/test_memory.py).

Tally (``ctx.tally``): an optional zero f32 ``(4,)`` input per layer name
that each op of that name takes beside ``x`` and ``w``. Its cotangent is the
work of the kernel path, summed over the name's kernel-variant contractions
that ran backward: ``[live 128 x 128 tiles, tiles of the padded grid, zero
levels, elements]`` (see :data:`TALLY_FIELDS`). ``Trainer._step``
differentiates with respect to it beside the parameters, so the counts reach
the step's metrics with no host callback; the other variants give it no
cotangent. One input per name keeps each count's sum inside its own layer's
backward: with one shared input, a scanned layer's out-projection count
waited for its in-projection's, which moved XLA's memory-space assignment of
the whole backward. Every backward rule runs
under the ``dither/bwd`` named scope (sub-scopes ``noise``, ``nsd``,
``pack``, ``matmul``, ``tally``), so a device profile can attribute its
time.

Variants (spec.variant):
  off     plain backprop
  paper   NSD in f32, products in the layer dtype      [faithful baseline]
  int8    NSD to (int8 k, Delta) + absmax-int8 x/w, both products on the
          int8 MXU path, rescaled on exit              [beyond paper, TPU]
  row     structured row dither                        [beyond paper, TPU]
  meprop  top-k magnitude comparator                   [paper's baseline]
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.core import int8 as int8lib
from repro.core import meprop as meproplib
from repro.core import nsd
from repro.core import rowdither
from repro.obs import metrics as statslib
from repro.obs.trace import annotate
from repro.core.policy import (
    KNOB_MEPROP_K_FRAC,
    KNOB_ROW_ALPHA,
    KNOB_S,
    VARIANT_INT8,
    VARIANT_KERNEL,
    VARIANT_MEPROP,
    VARIANT_PAPER,
    VARIANT_ROW,
    DitherCtx,
    StaticSpec,
)


# --------------------------------------------------------------------------
# residual store: encode at fwd time, decode at bwd time
# --------------------------------------------------------------------------

def _residlib():
    # lazy: repro.quant imports repro.core — a module-level import here
    # would run mid-way through core/__init__
    from repro import quant

    return quant


def encode_residual(x: jax.Array, key: jax.Array, spec: StaticSpec,
                    name: str):
    """Encode a saved forward residual under the layer's static mode and,
    when telemetry is on, record its measured / capacity / dense byte
    counts (wire-equivalent occupancy, HBM-resident buffers, legacy fp32
    store — see repro.quant for the distinction)."""
    codec = _residlib()
    if spec.residual in ("fp32", "remat"):
        enc = x  # identity: the residual tuple matches the legacy trace
    else:
        enc = codec.encode(spec.residual, x, codec.resid_key(key))
    if spec.collect_stats:
        statslib.emit_memory(
            spec.stats_tag + name,
            codec.measured_bytes(spec.residual, enc),
            codec.capacity_bytes(spec.residual, enc),
            codec.dense_nbytes(x.shape, x.dtype))
    return enc


def decode_residual(enc, spec: StaticSpec) -> jax.Array:
    if spec.residual in ("fp32", "remat"):
        return enc
    return _residlib().decode(spec.residual, enc)


def _record_footprint(ctx, r, name: str, x: jax.Array) -> None:
    """Trace-time byte accounting for repro.memory.accounting reports."""
    if ctx is None or ctx.mem_recorder is None or r is None:
        return
    codec = _residlib()
    ctx.mem_recorder[name] = (
        codec.stored_nbytes(r.spec.residual, x.shape, x.dtype),
        codec.dense_nbytes(x.shape, x.dtype))


# Identity marker whose custom fwd runs only under differentiation: remat
# layers hang their memory-telemetry row on it so rows appear exactly when
# a backward will consume the residual — the same semantics as the codec
# paths, whose emit lives in the op's own custom_vjp fwd.
@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _remat_emit(y, tag, nbytes):
    return y


def _re_fwd(y, tag, nbytes):
    # remat stores the raw op inputs: measured == capacity == dense
    statslib.emit_memory(tag, nbytes, nbytes, nbytes)
    return y, None


def _re_bwd(tag, nbytes, res, g):
    return (g,)


_remat_emit.defvjp(_re_fwd, _re_bwd)


def _tally_for(ctx, name: str):
    """The layer's tally input, or None where nothing is counted."""
    return None if ctx.tally is None else ctx.tally.get(name)


def _apply_op(op: Callable, x, w, r, name: str, tally=None):
    """Invoke a dithered op under the layer's resolved residual mode.

    Mode "remat" recomputes the op's forward in the VJP instead of
    consuming stored residuals (jax.checkpoint; spec/name stay static
    through the boundary). io_callback effects cannot live inside a
    checkpointed region, so remat layers run the op with telemetry
    stripped and emit their (identity) residual byte row through
    ``_remat_emit`` outside the checkpoint: a remat layer contributes no
    sparsity rows (and is invisible to the sparsity controller), which is
    the price of the recompute path and is pinned in tests/test_memory.py.
    """
    spec = r.spec
    if spec.residual != "remat":
        return op(x, w, r.key, r.knobs, tally, spec, name)
    collect = spec.collect_stats
    if collect:
        spec = dataclasses.replace(spec, collect_stats=False)
    y = jax.checkpoint(op, static_argnums=(5, 6))(
        x, w, r.key, r.knobs, tally, spec, name)
    if collect:
        y = _remat_emit(y, r.spec.stats_tag + name,
                        _residlib().dense_nbytes(x.shape, x.dtype))
    return y


# --------------------------------------------------------------------------
# cotangent quantization dispatch
# --------------------------------------------------------------------------

def quantize_cotangent(
    g: jax.Array, key: jax.Array, knobs: jax.Array, spec: StaticSpec,
    name: str
) -> jax.Array:
    """Apply the resolved quantizer to a pre-activation cotangent.

    ``knobs`` is the traced [s, meprop_k_frac, row_alpha] vector; ``spec``
    carries the static variant/telemetry switches.

    When ``spec.grad_codec`` is set, the registered quant codec replaces
    the variant's built-in quantizer: the cotangent takes the codec's
    fake-quant round trip (e.g. ``"int4@g32"`` grouped-scale), so new
    formats reach gradients without a new variant.
    """
    if spec.grad_codec is not None:
        quant = _residlib()
        out = quant.quantize(spec.grad_codec, g, key).astype(g.dtype)
        if spec.collect_stats:
            zero = 1.0 - jnp.mean((out != 0).astype(jnp.float32))
            bits = quant.parse_spec(spec.grad_codec).bits
            statslib.emit(
                spec.stats_tag + name,
                nsd.QuantStats(zero, jnp.float32(bits), jnp.float32(0)),
            )
        return out
    if spec.variant in (VARIANT_PAPER, VARIANT_INT8, VARIANT_KERNEL):
        with annotate("nsd"):
            delta = nsd.compute_delta(g, knobs[KNOB_S])
            k = nsd.nsd_indices(g, key, delta)
            if spec.collect_stats:
                statslib.emit(spec.stats_tag + name,
                              nsd.quant_stats(k, delta))
            return (k.astype(jnp.float32) * delta).astype(g.dtype)
    if spec.variant == VARIANT_ROW:
        out = rowdither.row_dither(g, key, knobs[KNOB_ROW_ALPHA])
        if spec.collect_stats:
            zero = 1.0 - jnp.mean((out != 0).astype(jnp.float32))
            statslib.emit(
                spec.stats_tag + name,
                nsd.QuantStats(zero, jnp.float32(32), jnp.float32(0)),
            )
        return out
    if spec.variant == VARIANT_MEPROP:
        k_frac = (spec.meprop_k_static if spec.meprop_k_static is not None
                  else knobs[KNOB_MEPROP_K_FRAC])
        out = meproplib.meprop_sparsify(g, k_frac)
        if spec.collect_stats:
            zero = 1.0 - jnp.mean((out != 0).astype(jnp.float32))
            statslib.emit(
                spec.stats_tag + name,
                nsd.QuantStats(zero, jnp.float32(32), jnp.float32(0)),
            )
        return out
    return g


# --------------------------------------------------------------------------
# VARIANT_KERNEL backward implementations (fused NSD + tile-skip matmuls)
# --------------------------------------------------------------------------

def _kernelops():
    # lazy: repro.kernels.ops imports repro.comm (wireformat) which imports
    # repro.core — a module-level import here would cycle
    from repro.kernels import ops

    return ops


# Columns of the tally's cotangent, in order (``Trainer._step`` names them
# ``dither_<field>`` in the step's metrics).
TALLY_FIELDS = ("tiles_live", "tiles", "zeros", "elements")


def _emit_kernel_stats(q, g2d: jax.Array, spec: StaticSpec, name: str):
    """Telemetry from the SAME quantized tensor the kernels consume.

    ``q.k`` is the fused kernel's output (zero-padded); slicing back to the
    live region makes the stats bit-identical to the paper path's
    ``nsd.quant_stats(nsd_indices(g2d, key, delta))`` for the same key —
    pinned in tests/test_kernels.py so the applied gradient and the
    telemetry can never diverge again.
    """
    if spec.collect_stats:
        k_live = q.k[: g2d.shape[0], : g2d.shape[1]].astype(jnp.int32)
        statslib.emit(spec.stats_tag + name, nsd.quant_stats(k_live, q.delta))


def _tile_counts(q, masks=None) -> jax.Array:
    """The tally's cotangent for one quantized cotangent ``q``: the live
    tiles of ``masks`` (the tile masks the matmuls consume; default
    ``[q.mask]``), all their tiles, and the zero levels and elements of
    ``q``'s live region. Reductions of tile maps only: the padding
    quantizes to zero, so ``q.nnz`` counts the live region's non-zeros."""
    masks = [q.mask] if masks is None else masks
    with annotate("tally"):
        m, n = q.shape
        live = sum(jnp.sum(mk, dtype=jnp.int32) for mk in masks)
        zeros = m * n - jnp.sum(q.nnz, dtype=jnp.int32)
        return jnp.stack([live, sum(mk.size for mk in masks), zeros,
                          m * n]).astype(jnp.float32)


def _dense_kernel_bwd(x, w, key, knobs, spec, name, g):
    """Tile-skipping backward for y = x @ w (any shape; padded to tiles).
    Returns (dx, dw, tile counts)."""
    ops = _kernelops()
    kdim = x.shape[-1]
    g2d = g.reshape(-1, g.shape[-1])
    q = ops.quantize_and_mask(g2d, key, knobs[KNOB_S])
    _emit_kernel_stats(q, g2d, spec, name)
    dx2d, dw = ops.bsp_backward_from_quantized(
        q, x.reshape(-1, kdim), w, int8_operands=True)
    return (dx2d.reshape(x.shape).astype(x.dtype), dw.astype(w.dtype),
            _tile_counts(q))


@functools.lru_cache(maxsize=None)
def _conv_kernel_bwd(strides, padding, lhs_dilation, rhs_dilation,
                     feature_group_count):
    """Kernel-variant backward for conv2d via im2col.

    conv(x, w) == patches(x) @ w_mat with the patch feature axis ordered
    (Ci, kh, kw) — so both backward products are exactly the dense layer's
    tile-skipping matmuls on the im2col matrix, and dx folds back through
    the exact vjp of the (linear) patch extraction. Grouped or
    lhs-dilated convs fall back to the generic quantized path (counted in
    ``repro.kernels.ops.KERNEL_FALLBACKS``, never silent).
    """

    def kernel_bwd(x, w, key, knobs, spec, name, g):
        if feature_group_count != 1 or tuple(lhs_dilation) != (1, 1):
            _kernelops().note_fallback("conv:groups-or-lhs-dilation", name)
            return None
        ops = _kernelops()
        kh, kw, ci, co = w.shape
        kk = kh * kw * ci

        def patches_fn(xx):
            return jax.lax.conv_general_dilated_patches(
                xx, (kh, kw), strides, padding,
                rhs_dilation=rhs_dilation,
                dimension_numbers=("NHWC", "HWIO", "NHWC"))

        cols, unpatch = jax.vjp(patches_fn, x)
        g2d = g.reshape(-1, co)
        q = ops.quantize_and_mask(g2d, key, knobs[KNOB_S])
        _emit_kernel_stats(q, g2d, spec, name)
        w_mat = w.transpose(2, 0, 1, 3).reshape(kk, co)
        dcols2d, dw_mat = ops.bsp_backward_from_quantized(
            q, cols.reshape(-1, kk), w_mat, int8_operands=True)
        dx = unpatch(dcols2d.reshape(cols.shape))[0]
        dw = dw_mat.reshape(ci, kh, kw, co).transpose(1, 2, 0, 3)
        return dx.astype(x.dtype), dw.astype(w.dtype), _tile_counts(q)

    return kernel_bwd


def _einsum_form(spec: str):
    """Classify a two-operand einsum for the kernel backward.

    Returns "dense2d" for ``...k,kn->...n`` (shared 2-D weight: flatten and
    run the dense pipeline), "batched" for ``B...k,Bkn->B...n`` (leading
    shared batch axis, per-slice 2-D matmul — the MoE expert-FFN shape), or
    None (unsupported: counted fallback to the generic quantized path).
    """
    if "->" not in spec or "." in spec:
        return None
    ins, out = spec.split("->")
    if "," not in ins:
        return None
    a, b = ins.split(",")
    if len(set(a)) != len(a) or len(set(b)) != len(b):
        return None
    if len(b) == 2 and len(a) >= 2 and a[-1] == b[0] \
            and out == a[:-1] + b[1] and b[1] not in a:
        return "dense2d"
    if len(b) == 3 and len(a) >= 3 and a[0] == b[0] \
            and a[-1] == b[1] and out == a[0] + a[1:-1] + b[2] \
            and b[2] not in a:
        return "batched"
    return None


@functools.lru_cache(maxsize=None)
def _einsum_kernel_bwd(spec_str: str):
    form = _einsum_form(spec_str)

    def kernel_bwd(x, w, key, knobs, spec, name, g):
        ops = _kernelops()
        if form is None:
            ops.note_fallback("einsum:unsupported-form:" + spec_str, name)
            return None
        if form == "dense2d":
            return _dense_kernel_bwd(x, w, key, knobs, spec, name, g)
        # batched: per-slice matmuls share ONE per-tensor quantization
        # (delta over the whole cotangent, noise over its full shape) so
        # the quantized values are bit-identical to the paper path; each
        # slice derives its own tile mask from its packed bitmap.
        n_b = x.shape[0]
        fdim = g.shape[-1]
        g2d = g.reshape(-1, fdim)
        q_full = ops.quantize_and_mask(g2d, key, knobs[KNOB_S])
        _emit_kernel_stats(q_full, g2d, spec, name)
        k3 = q_full.k[: g2d.shape[0], :fdim].reshape(n_b, -1, fdim)
        x3 = x.reshape(n_b, -1, x.shape[-1])
        dxs, dws, masks = [], [], []
        for e in range(n_b):
            q_e = ops.quantized_from_indices(k3[e], q_full.delta)
            dx_e, dw_e = ops.bsp_backward_from_quantized(
                q_e, x3[e], w[e], int8_operands=True)
            dxs.append(dx_e)
            dws.append(dw_e)
            masks.append(q_e.mask)
        dx = jnp.stack(dxs).reshape(x.shape).astype(x.dtype)
        dw = jnp.stack(dws).astype(w.dtype)
        return dx, dw, _tile_counts(q_full, masks)

    return kernel_bwd


# --------------------------------------------------------------------------
# generic dithered op: works for any two-operand primal (conv, einsum, ...)
# --------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _make_dithered_op(primal_fn: Callable,
                      kernel_bwd: Optional[Callable] = None) -> Callable:
    """Wrap ``primal_fn(x, w) -> y`` so its bwd quantizes the cotangent once
    and pushes it through the *exact* vjp of the primal — this is precisely
    the paper's recipe and is correct for any linear primal.

    ``kernel_bwd(x, w, key, knobs, spec, name, g) -> (dx, dw, counts) |
    None`` supplies the VARIANT_KERNEL tile-skipping backward, ``counts``
    being the tally's cotangent; returning None (a counted structural
    fallback) drops to the generic quantized path.
    """

    @functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
    def op(x, w, key, knobs, tally, spec, name):
        return primal_fn(x, w)

    def fwd(x, w, key, knobs, tally, spec, name):
        enc = encode_residual(x, key, spec, name)
        return primal_fn(x, w), (enc, w, key, knobs, tally)

    def rule(spec, name, enc, w, key, knobs, g):
        x = decode_residual(enc, spec)
        if spec.variant == VARIANT_KERNEL and kernel_bwd is not None \
                and spec.grad_codec is None:
            out = kernel_bwd(x, w, key, knobs, spec, name, g)
            if out is not None:
                return out
        gq = quantize_cotangent(g, key, knobs, spec, name)
        _, vjp = jax.vjp(primal_fn, x, w)
        dx, dw = vjp(gq)
        return dx, dw, None

    def bwd(spec, name, res, g):
        enc, w, key, knobs, tally = res
        with annotate("dither/bwd"):
            dx, dw, counts = rule(spec, name, enc, w, key, knobs, g)
        return dx, dw, None, None, _tally_cotangent(tally, counts)

    op.defvjp(fwd, bwd)
    return op


# --------------------------------------------------------------------------
# dense (the paper's fully-connected case) with an explicit int8 backward
# --------------------------------------------------------------------------

def _plain_matmul(x, w):
    return jax.lax.dot_general(
        x, w, dimension_numbers=(((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=x.dtype,
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _dithered_dense(x, w, key, knobs, tally, spec, name):
    return _plain_matmul(x, w)


def _dd_fwd(x, w, key, knobs, tally, spec, name):
    enc = encode_residual(x, key, spec, name)
    return _plain_matmul(x, w), (enc, w, key, knobs, tally)


def _dd_bwd(spec, name, res, g):
    enc, w, key, knobs, tally = res
    with annotate("dither/bwd"):
        dx, dw, counts = _dense_rule(spec, name, enc, w, key, knobs, g)
    return dx, dw, None, None, _tally_cotangent(tally, counts)


def _tally_cotangent(tally, counts):
    """The tally's cotangent: the kernel path's counts, else none."""
    if tally is None or counts is None:
        return None
    return counts


def _dense_rule(spec, name, enc, w, key, knobs, g):
    """The dense backward under the layer's variant: (dx, dw, counts),
    counts being None off the kernel path."""
    x = decode_residual(enc, spec)
    s = knobs[KNOB_S]
    kdim = x.shape[-1]
    x2d = x.reshape(-1, kdim)
    g2d = g.reshape(-1, g.shape[-1])

    # a grad_codec overrides the variant's built-in quantizer: skip the
    # NSD-specific kernel/int8 fast paths and take the generic route below
    if spec.variant == VARIANT_KERNEL and spec.grad_codec is None:
        # Pallas path: fused NSD quantize + tile-skipping int8 matmuls
        # (interpret mode on CPU; compiled VMEM kernels on TPU). Any layer
        # shape: operands are zero-padded to tile multiples, the padding
        # tiles quantize to all-zero and are masked off.
        return _dense_kernel_bwd(x, w, key, knobs, spec, name, g)

    if spec.variant == VARIANT_INT8 and spec.grad_codec is None:
        # NSD indices ARE an int8 tensor; x and w get absmax int8. Both
        # backward products then run on the int8 MXU path (2x bf16 on v5e).
        with annotate("nsd"):
            delta = nsd.compute_delta(g2d, s)
            k = nsd.nsd_indices(g2d, key, delta).astype(jnp.int8)
            if spec.collect_stats:
                statslib.emit(spec.stats_tag + name,
                              nsd.quant_stats(k, delta))
        with annotate("matmul"):
            xq = int8lib._quantize_int8(x2d)
            wq = int8lib._quantize_int8(w)
            # dx = g~ @ W^T : contract over the output dim
            dx2d = jax.lax.dot_general(
                k, wq.q, dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.int32,
            ).astype(jnp.float32) * (delta * wq.scale)
            # dW = x^T @ g~ : contract over the row (token) dim
            dw = jax.lax.dot_general(
                xq.q, k, dimension_numbers=(((0,), (0,)), ((), ())),
                preferred_element_type=jnp.int32,
            ).astype(jnp.float32) * (xq.scale * delta)
        return (dx2d.astype(x.dtype).reshape(x.shape), dw.astype(w.dtype),
                None)

    gq = quantize_cotangent(g2d, key, knobs, spec, name)
    dx2d = jax.lax.dot_general(
        gq, w, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=gq.dtype,
    )
    dw = jax.lax.dot_general(
        x2d, gq, dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=x2d.dtype,
    )
    return dx2d.astype(x.dtype).reshape(x.shape), dw.astype(w.dtype), None


_dithered_dense.defvjp(_dd_fwd, _dd_bwd)


def dense(
    x: jax.Array,
    w: jax.Array,
    b: Optional[jax.Array] = None,
    *,
    ctx: Optional[DitherCtx] = None,
    name: str = "dense",
) -> jax.Array:
    """y = x @ w (+ b); dithered backward when resolution covers ``name``.

    When ctx is None (inference / serving / baseline) or the resolved
    per-layer policy is off, this is a plain matmul with no custom_vjp in
    the trace at all.
    """
    r = ctx.resolve(name) if ctx is not None else None
    if r is not None:
        _record_footprint(ctx, r, name, x)
        y = _apply_op(_dithered_dense, x, w, r, name, _tally_for(ctx, name))
    else:
        y = _plain_matmul(x, w)
    if b is not None:
        y = y + b
    return y


# --------------------------------------------------------------------------
# conv2d (the paper's convolutional case) — exact vjp of the quantized
# cotangent via the generic wrapper
# --------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _conv_primal(strides, padding, lhs_dilation, rhs_dilation, feature_group_count):
    def primal(x, w):  # NHWC x HWIO -> NHWC
        return jax.lax.conv_general_dilated(
            x, w,
            window_strides=strides,
            padding=padding,
            lhs_dilation=lhs_dilation,
            rhs_dilation=rhs_dilation,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=feature_group_count,
        )
    return primal


def conv2d(
    x: jax.Array,
    w: jax.Array,
    b: Optional[jax.Array] = None,
    *,
    strides=(1, 1),
    padding="SAME",
    lhs_dilation=(1, 1),
    rhs_dilation=(1, 1),
    feature_group_count: int = 1,
    ctx: Optional[DitherCtx] = None,
    name: str = "conv",
) -> jax.Array:
    primal = _conv_primal(
        tuple(strides), padding if isinstance(padding, str) else tuple(padding),
        tuple(lhs_dilation), tuple(rhs_dilation), feature_group_count,
    )
    kernel_bwd = _conv_kernel_bwd(
        tuple(strides), padding if isinstance(padding, str) else tuple(padding),
        tuple(lhs_dilation), tuple(rhs_dilation), feature_group_count,
    )
    r = ctx.resolve(name) if ctx is not None else None
    if r is not None:
        _record_footprint(ctx, r, name, x)
        y = _apply_op(_make_dithered_op(primal, kernel_bwd), x, w, r, name,
                      _tally_for(ctx, name))
    else:
        y = primal(x, w)
    if b is not None:
        y = y + b
    return y


# --------------------------------------------------------------------------
# two-operand einsum (expert FFNs, attention projections with fused heads)
# --------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _einsum_primal(spec: str):
    def primal(x, w):
        return jnp.einsum(spec, x, w)
    return primal


def dithered_einsum(
    spec: str,
    x: jax.Array,
    w: jax.Array,
    *,
    ctx: Optional[DitherCtx] = None,
    name: str = "einsum",
) -> jax.Array:
    """einsum('...,...->...', x, w) with dithered backward on the cotangent."""
    primal = _einsum_primal(spec)
    r = ctx.resolve(name) if ctx is not None else None
    if r is not None:
        _record_footprint(ctx, r, name, x)
        return _apply_op(_make_dithered_op(primal, _einsum_kernel_bwd(spec)),
                         x, w, r, name, _tally_for(ctx, name))
    return primal(x, w)
