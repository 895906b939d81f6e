"""Built-in codecs: the repo's one home for quantized representations.

Every format that used to live in a subsystem-private encode/decode pair is
a registered :class:`repro.quant.registry.Codec` here, resolved from spec
strings through ``parse_spec``:

    fp32         identity passthrough (the parity arm)
    remat        storage *mode*, not a format: identity here; the memory
                 subsystem wraps the op in jax.checkpoint instead of storing
    bf16         2-byte truncation; exact for bf16-representable values
    int8         affine per-row (residual-store lineage): q = round((x -
                 min_row)/scale_row) - 128, scale_row = range_row/255;
                 error bounded by scale_row/2 per element
    nsd[@S]      the paper's operator in the comm wire layout
                 (``repro.quant.wire``); bit-exact vs ``repro.core.nsd``
                 for the same key; jnp + Pallas backends
    int8_absmax  per-tensor symmetric absmax (Banner-style forward path;
                 ``core/int8`` lineage); optional stochastic rounding;
                 compute_on_packed = the int8 MXU matmul
    int4[@gG]    4-bit grouped-scale, two values per stored byte, one f32
                 scale per G elements (default 32); deterministic
                 round-to-nearest, error bounded by scale_group/2. NEW in
                 the quant subsystem — reaches gradients, wire, residuals,
                 KV pages and moments with no per-subsystem code.
    m8           optimizer momentum: per-row symmetric absmax int8,
                 deterministic (re-encoded every step without a key)
    u8           optimizer second moment: sqrt-domain per-row absmax uint8
                 (v >= 0; quantize sqrt(v), decode square) — relative
                 resolution where adam's rsqrt needs it

NSD/int8 behavior is pinned bit-exact against the pre-migration
implementations (``repro.memory.codec`` / ``repro.comm.wireformat`` /
``repro.core.nsd`` / ``repro.core.int8`` — now deprecated shims over this
module) by tests/test_quant.py and the zero-band suite gates.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import int8 as int8lib
from repro.core import nsd
from repro.quant import wire
from repro.quant.registry import (Codec, _nelems, dense_nbytes, get_codec,
                                  parse_spec, register)
from repro.quant.spec import QuantSpec

# "nsd" residuals want fidelity (they feed the weight-gradient product),
# so the default dither scale is gentler than the gradient-side s=2.
DEFAULT_NSD_S = 1.0

DEFAULT_INT4_GROUP = 32

# Salt folded into the layer key for the residual encode so the activation
# dither draws an RNG stream independent of the backward's cotangent dither.
RESID_SALT = 0x4E5D


def resid_key(key: jax.Array) -> jax.Array:
    """The residual-encode RNG stream for a layer's per-step key."""
    return jax.random.fold_in(key, RESID_SALT)


# ---------------------------------------------------------------------------
# canonical quantize helpers (the non-deprecated homes of the core math)
# ---------------------------------------------------------------------------

def absmax_int8(x: jax.Array,
                key: Optional[jax.Array] = None) -> int8lib.QuantTensor:
    """Per-tensor absmax int8; stochastic rounding when ``key`` is given.

    The canonical home of ``repro.core.int8.quantize_int8`` (now a
    deprecated shim over this function); math unchanged, bit-exact.
    """
    scale = int8lib.absmax_scale(x)
    v = x.astype(jnp.float32) / scale
    if key is not None:
        v = v + jax.random.uniform(key, x.shape, jnp.float32, -0.5, 0.5)
    q = jnp.clip(jnp.round(v), -127, 127).astype(jnp.int8)
    return int8lib.QuantTensor(q=q, scale=scale)


def nsd_fakequant(x: jax.Array, key: jax.Array, s: float) -> jax.Array:
    """Paper-faithful NSD fake-quant: Delta * k in x.dtype.

    The canonical home of ``repro.core.nsd.nsd_quantize`` (deprecated
    shim); composes the undeprecated core primitives, bit-exact.
    """
    delta = nsd.compute_delta(x, s)
    k = nsd.nsd_indices(x, key, delta)
    return (k.astype(jnp.float32) * delta).astype(x.dtype)


def nsd_int8(x: jax.Array, key: jax.Array, s: float) -> nsd.QuantizedGrad:
    """NSD to (int8 k, f32 Delta) — home of ``nsd.nsd_quantize_int8``."""
    delta = nsd.compute_delta(x, s)
    k = nsd.nsd_indices(x, key, delta)
    return nsd.QuantizedGrad(k=k.astype(jnp.int8), delta=delta)


# ---------------------------------------------------------------------------
# encoded containers (jit-safe: static shape/dtype metadata)
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Bf16Residual:
    data: jax.Array  # bf16, original shape
    dtype: str = dataclasses.field(metadata=dict(static=True),
                                   default="float32")


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Int8Residual:
    """Affine per-row int8: value ~= (q + 128) * scale + lo, row-wise."""

    q: jax.Array  # int8 (rows, cols) — rows = prod(shape[:-1])
    scale: jax.Array  # f32 (rows, 1): range / 255 (guarded > 0)
    lo: jax.Array  # f32 (rows, 1): per-row minimum
    shape: Tuple[int, ...] = dataclasses.field(metadata=dict(static=True),
                                               default=())
    dtype: str = dataclasses.field(metadata=dict(static=True),
                                   default="float32")


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Int4Grouped:
    """4-bit grouped-scale: two values per byte, one f32 scale per group.

    ``packed[g, b]`` holds elements ``2b`` (low nibble) and ``2b+1`` (high
    nibble) of group ``g``, each an unsigned 4-bit code ``q + 8`` with
    ``q = round(x / scale_g) in [-7, 7]``.
    """

    packed: jax.Array  # uint8 (n_groups, group // 2)
    scale: jax.Array  # f32 (n_groups, 1): absmax / 7 (guarded > 0)
    shape: Tuple[int, ...] = dataclasses.field(metadata=dict(static=True),
                                               default=())
    dtype: str = dataclasses.field(metadata=dict(static=True),
                                   default="float32")


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class RowQuant8:
    """Per-row symmetric absmax int8: value ~= q * scale, row-wise."""

    q: jax.Array  # int8 (rows, cols)
    scale: jax.Array  # f32 (rows, 1): absmax / 127 (guarded > 0)
    shape: Tuple[int, ...] = dataclasses.field(metadata=dict(static=True),
                                               default=())
    dtype: str = dataclasses.field(metadata=dict(static=True),
                                   default="float32")


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SqrtRowQuant8:
    """Sqrt-domain per-row uint8 for non-negative tensors: v ~= (q*scale)^2."""

    q: jax.Array  # uint8 (rows, cols): round(sqrt(v) / scale)
    scale: jax.Array  # f32 (rows, 1): max_row(sqrt(v)) / 255 (guarded > 0)
    shape: Tuple[int, ...] = dataclasses.field(metadata=dict(static=True),
                                               default=())
    dtype: str = dataclasses.field(metadata=dict(static=True),
                                   default="float32")


def _rows_cols(shape) -> Tuple[int, int]:
    cols = int(shape[-1]) if shape else 1
    return _nelems(shape) // cols, cols


def _no_param(name: str, param: str) -> None:
    if param:
        raise ValueError(f"codec {name!r} takes no @-parameter, got "
                         f"{param!r}")


# ---------------------------------------------------------------------------
# codecs
# ---------------------------------------------------------------------------

class Fp32Codec(Codec):
    name = "fp32"
    needs_key = False

    def make_spec(self, param: str) -> QuantSpec:
        _no_param(self.name, param)
        return QuantSpec(codec=self.name, bits=32, layout="dense")

    def encode(self, spec, x, key=None):
        return x

    def decode(self, spec, enc):
        return enc

    def stored_nbytes(self, spec, shape, dtype) -> int:
        return dense_nbytes(shape, dtype)


class RematMode(Fp32Codec):
    """Not a format: the memory subsystem reruns the forward instead of
    storing. Registered so ``"remat"`` validates through the one front
    door; identity + dense accounting here (honest: remat keeps the raw op
    inputs live across the checkpoint boundary)."""

    name = "remat"


class Bf16Codec(Codec):
    name = "bf16"
    needs_key = False

    def make_spec(self, param: str) -> QuantSpec:
        _no_param(self.name, param)
        return QuantSpec(codec=self.name, bits=16, layout="dense")

    def encode(self, spec, x, key=None):
        return Bf16Residual(data=x.astype(jnp.bfloat16),
                            dtype=jnp.dtype(x.dtype).name)

    def decode(self, spec, enc):
        return enc.data.astype(jnp.dtype(enc.dtype))

    def stored_nbytes(self, spec, shape, dtype) -> int:
        return _nelems(shape) * 2

    def capacity_bytes(self, spec, enc) -> int:
        return _nelems(enc.data.shape) * 2

    def error_bound(self, spec, enc):
        # bf16 keeps 8 significand bits: |x - bf16(x)| <= 2^-8 |x|, so in
        # terms of the DECODED value the safe bound is 2^-7 |decoded|.
        return jnp.abs(self.decode(spec, enc)) * jnp.float32(2.0 ** -7)


class Int8RowAffineCodec(Codec):
    name = "int8"
    needs_key = False

    def make_spec(self, param: str) -> QuantSpec:
        _no_param(self.name, param)
        return QuantSpec(codec=self.name, bits=8, granularity="row",
                         layout="row-affine")

    def encode(self, spec, x, key=None):
        cols = x.shape[-1] if x.ndim else 1
        x2 = x.astype(jnp.float32).reshape(-1, cols)
        lo = jnp.min(x2, axis=1, keepdims=True)
        hi = jnp.max(x2, axis=1, keepdims=True)
        scale = jnp.maximum(hi - lo, jnp.finfo(jnp.float32).tiny) / 255.0
        q = jnp.round((x2 - lo) / scale) - 128.0
        q = jnp.clip(q, -128, 127).astype(jnp.int8)
        return Int8Residual(q=q, scale=scale, lo=lo, shape=tuple(x.shape),
                            dtype=jnp.dtype(x.dtype).name)

    def decode(self, spec, enc):
        x2 = (enc.q.astype(jnp.float32) + 128.0) * enc.scale + enc.lo
        return x2.reshape(enc.shape).astype(jnp.dtype(enc.dtype))

    def stored_nbytes(self, spec, shape, dtype) -> int:
        rows, _ = _rows_cols(shape)
        return _nelems(shape) + rows * 8  # q int8 + per-row (scale, lo) f32

    def error_bound(self, spec, enc):
        return jnp.broadcast_to(enc.scale * 0.5,
                                enc.q.shape).reshape(enc.shape)


class NsdCodec(Codec):
    """The paper's operator in wire layout; see ``repro.quant.wire``."""

    name = "nsd"
    needs_key = True

    def __init__(self):
        self.backends = {
            "encode": {"jnp": None, "pallas": None},
            "decode": {"jnp": None, "pallas": None},
            "compute_on_packed": {"jnp": None, "pallas": None},
        }

    def make_spec(self, param: str) -> QuantSpec:
        s = float(param) if param else DEFAULT_NSD_S
        if not s > 0:
            raise ValueError(f"nsd spec: s must be > 0, got {s}")
        return QuantSpec(codec=self.name, bits=8, granularity="chunk",
                         dither="uniform", layout="bitmap+levels", param=s,
                         chunk=wire.DEFAULT_CHUNK)

    def encode(self, spec, x, key, backend: str = "jnp"):
        if key is None:
            raise ValueError("nsd encode needs an RNG key (dithered codec)")
        return wire.pack_nsd(x, key, spec.param,
                             spec.chunk or wire.DEFAULT_CHUNK,
                             backend=backend)

    def decode(self, spec, enc, backend: str = "jnp"):
        return wire.unpack_nsd(enc, backend=backend)

    def stored_nbytes(self, spec, shape, dtype) -> int:
        chunk = spec.chunk or wire.DEFAULT_CHUNK
        n = _nelems(shape)
        padded = ((n + chunk - 1) // chunk) * chunk
        n_chunks = padded // chunk
        # levels capacity + bitmap + per-chunk deltas + nnz scalar
        return padded + padded // 8 + 4 * n_chunks + 4

    def measured_bytes(self, spec, enc) -> jax.Array:
        return enc.wire_bytes()

    def error_bound(self, spec, enc):
        # NSD error is < Delta per element (|x + nu - Delta k| <= Delta/2,
        # |nu| <= Delta/2). Nothing in the encoding bounds it where a level
        # sits at the clip (|k| = INT8_CLIP may have been cut off) or where
        # Delta is 0 (std 0, as for one element: every level is 0 whatever
        # x held); the bound there is inf.
        n = _nelems(enc.shape)
        per_elem = jnp.broadcast_to(
            enc.deltas[:, None], (enc.n_chunks, enc.chunk)).reshape(-1)
        k = wire._expand(enc.levels,
                         wire.unpack_bitmap(enc.bitmap).reshape(-1))
        unbounded = ((per_elem <= 0.0)
                     | (jnp.abs(k.astype(jnp.int32)) >= nsd.INT8_CLIP))
        return jnp.where(unbounded, jnp.inf, per_elem)[:n].reshape(enc.shape)

    def compute_on_packed(self, spec, enc, x, w, *, backend: str = "jnp"):
        """Both backward products of y = x @ w from the packed cotangent.

        ``enc`` is the PackedNSD of the 2-D pre-activation gradient g~
        (T, N); x: (T, K); w: (K, N). The pallas backend rebuilds the int8
        k tensor + tile mask from the wire bitmap and runs the
        tile-skipping bsp matmuls (``repro.kernels.ops``); the jnp
        reference dequantizes and runs dense products.
        """
        T, N = (int(d) for d in enc.shape)
        if backend == "pallas":
            from repro.kernels import ops

            mask = wire.unpack_bitmap(enc.bitmap).reshape(-1)
            k2d = wire._expand(enc.levels, mask)[: T * N].reshape(T, N)
            q = ops.quantized_from_indices(k2d, enc.deltas[0])
            return ops.bsp_backward_from_quantized(q, x, w,
                                                   int8_operands=True)
        g2d = wire.unpack_nsd(enc).astype(jnp.float32)
        x2d = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
        dx = (g2d @ w.astype(jnp.float32).T).reshape(x.shape)
        dw = x2d.T @ g2d
        return dx.astype(x.dtype), dw.astype(w.dtype)


class Int8AbsmaxCodec(Codec):
    """Per-tensor symmetric absmax int8 (``core/int8`` lineage)."""

    name = "int8_absmax"
    needs_key = False  # key optional: stochastic rounding

    def make_spec(self, param: str) -> QuantSpec:
        _no_param(self.name, param)
        return QuantSpec(codec=self.name, bits=8,
                         dither="stochastic-round", layout="dense")

    def encode(self, spec, x, key=None):
        return absmax_int8(x, key)

    def decode(self, spec, enc):
        return enc.q.astype(jnp.float32) * enc.scale

    def stored_nbytes(self, spec, shape, dtype) -> int:
        return _nelems(shape) + 4

    def capacity_bytes(self, spec, enc) -> int:
        return _nelems(enc.q.shape) + 4

    def error_bound(self, spec, enc):
        # scale/2 deterministic; the stochastic-rounding path adds +-0.5
        # before rounding, so the safe bound covering both is one scale.
        return jnp.broadcast_to(enc.scale, enc.q.shape)

    def compute_on_packed(self, spec, enc_x, enc_w, *, backend: str = "jnp",
                          out_dtype=jnp.float32):
        """int8 x int8 -> int32 matmul, rescaled on exit (MXU-native)."""
        return int8lib.int8_matmul(enc_x, enc_w, out_dtype=out_dtype)


class Int4GroupedCodec(Codec):
    """4-bit grouped-scale — the quant subsystem's proof of 'one PR'."""

    name = "int4"
    needs_key = False

    def make_spec(self, param: str) -> QuantSpec:
        raw = param.lstrip("g") if param else ""
        group = int(raw) if raw else DEFAULT_INT4_GROUP
        if group < 2 or group % 2:
            raise ValueError(
                f"int4 spec: group must be even and >= 2, got {group}")
        return QuantSpec(codec=self.name, bits=4, granularity="group",
                         group=group, layout="grouped", param=float(group))

    def encode(self, spec, x, key=None):
        g = spec.group
        flat = x.astype(jnp.float32).reshape(-1)
        pad = (-flat.shape[0]) % g
        flat = jnp.pad(flat, (0, pad))
        g2 = flat.reshape(-1, g)
        scale = jnp.maximum(jnp.max(jnp.abs(g2), axis=1, keepdims=True),
                            jnp.finfo(jnp.float32).tiny) / 7.0
        v = (jnp.clip(jnp.round(g2 / scale), -7, 7) + 8).astype(jnp.uint8)
        packed = (v[:, 0::2] | (v[:, 1::2] << 4)).astype(jnp.uint8)
        return Int4Grouped(packed=packed, scale=scale, shape=tuple(x.shape),
                           dtype=jnp.dtype(x.dtype).name)

    def decode(self, spec, enc):
        lo = (enc.packed & 0xF).astype(jnp.int32) - 8
        hi = (enc.packed >> 4).astype(jnp.int32) - 8
        q = jnp.stack([lo, hi], axis=-1).reshape(enc.packed.shape[0], -1)
        vals = (q.astype(jnp.float32) * enc.scale).reshape(-1)
        n = _nelems(enc.shape)
        return vals[:n].reshape(enc.shape).astype(jnp.dtype(enc.dtype))

    def stored_nbytes(self, spec, shape, dtype) -> int:
        g = spec.group
        n = _nelems(shape)
        n_groups = (n + g - 1) // g
        return n_groups * (g // 2) + 4 * n_groups  # nibbles + f32 scales

    def error_bound(self, spec, enc):
        g = spec.group
        n = _nelems(enc.shape)
        per_elem = jnp.broadcast_to(enc.scale * 0.5,
                                    (enc.scale.shape[0], g)).reshape(-1)
        return per_elem[:n].reshape(enc.shape)


class M8MomentCodec(Codec):
    """Optimizer momentum: per-row symmetric absmax int8, deterministic."""

    name = "m8"
    needs_key = False

    def make_spec(self, param: str) -> QuantSpec:
        _no_param(self.name, param)
        return QuantSpec(codec=self.name, bits=8, granularity="row",
                         layout="row-affine")

    def encode(self, spec, x, key=None):
        cols = x.shape[-1] if x.ndim else 1
        x2 = x.astype(jnp.float32).reshape(-1, cols)
        amax = jnp.max(jnp.abs(x2), axis=1, keepdims=True)
        scale = jnp.maximum(amax, jnp.finfo(jnp.float32).tiny) / 127.0
        q = jnp.clip(jnp.round(x2 / scale), -127, 127).astype(jnp.int8)
        return RowQuant8(q=q, scale=scale, shape=tuple(x.shape),
                         dtype=jnp.dtype(x.dtype).name)

    def decode(self, spec, enc):
        x2 = enc.q.astype(jnp.float32) * enc.scale
        return x2.reshape(enc.shape).astype(jnp.dtype(enc.dtype))

    def stored_nbytes(self, spec, shape, dtype) -> int:
        rows, _ = _rows_cols(shape)
        return _nelems(shape) + rows * 4

    def error_bound(self, spec, enc):
        return jnp.broadcast_to(enc.scale * 0.5,
                                enc.q.shape).reshape(enc.shape)


class U8SqrtMomentCodec(Codec):
    """Optimizer second moment: sqrt-domain per-row uint8 (v >= 0)."""

    name = "u8"
    needs_key = False

    def make_spec(self, param: str) -> QuantSpec:
        _no_param(self.name, param)
        return QuantSpec(codec=self.name, bits=8, granularity="row",
                         layout="row-affine")

    def encode(self, spec, x, key=None):
        cols = x.shape[-1] if x.ndim else 1
        r = jnp.sqrt(jnp.maximum(x.astype(jnp.float32), 0.0)
                     ).reshape(-1, cols)
        rmax = jnp.max(r, axis=1, keepdims=True)
        scale = jnp.maximum(rmax, jnp.finfo(jnp.float32).tiny) / 255.0
        q = jnp.clip(jnp.round(r / scale), 0, 255).astype(jnp.uint8)
        return SqrtRowQuant8(q=q, scale=scale, shape=tuple(x.shape),
                             dtype=jnp.dtype(x.dtype).name)

    def decode(self, spec, enc):
        r = enc.q.astype(jnp.float32) * enc.scale
        return jnp.square(r).reshape(enc.shape).astype(jnp.dtype(enc.dtype))

    def stored_nbytes(self, spec, shape, dtype) -> int:
        rows, _ = _rows_cols(shape)
        return _nelems(shape) + rows * 4

    def error_bound(self, spec, enc):
        # |v - v_hat| = |r - r_hat| (r + r_hat) <= (s/2)(2 r_hat + s/2)
        # with r-domain error <= scale/2 and r <= r_hat + s/2.
        s = jnp.broadcast_to(enc.scale, enc.q.shape)
        r_hat = enc.q.astype(jnp.float32) * enc.scale
        return ((s * 0.5) * (2.0 * r_hat + s * 0.5)).reshape(enc.shape)


register(Fp32Codec())
register(RematMode())
register(Bf16Codec())
register(Int8RowAffineCodec())
register(NsdCodec())
register(Int8AbsmaxCodec())
register(Int4GroupedCodec())
register(M8MomentCodec())
register(U8SqrtMomentCodec())


# ---------------------------------------------------------------------------
# legacy mode grammar (repro.memory.codec compat, now registry-backed)
# ---------------------------------------------------------------------------

MODE_FP32 = "fp32"
MODE_BF16 = "bf16"
MODE_INT8 = "int8"
MODE_NSD = "nsd"
MODE_REMAT = "remat"
MODES = (MODE_FP32, MODE_BF16, MODE_INT8, MODE_NSD, MODE_REMAT)


def parse_mode(mode: str) -> Tuple[str, float]:
    """``"nsd@0.5"`` -> ("nsd", 0.5); other specs get (codec, 0.0).

    The legacy ``repro.memory.codec`` grammar, generalized: any registered
    codec spec parses (so ``"int4@g32"`` is a valid residual/KV mode); the
    (kind, param) pair keeps its historical meaning for the original five,
    and an unregistered codec keeps the historical error wording.
    """
    try:
        spec = parse_spec(mode)
    except ValueError as e:
        if "unknown codec" in str(e):
            raise ValueError(
                f"unknown residual mode {mode!r}; a registered quant codec "
                f"spec (see repro.quant.codec_names)") from None
        raise
    return spec.codec, spec.param if spec.codec == MODE_NSD else 0.0


def validate_mode(mode: str) -> str:
    parse_mode(mode)
    return mode


# ---------------------------------------------------------------------------
# facade dispatch: one entry point per capability
# ---------------------------------------------------------------------------

def encode(mode: str, x: jax.Array, key: Optional[jax.Array] = None):
    """Encode under a spec string; fp32/remat return ``x`` itself."""
    spec = parse_spec(mode)
    if spec.codec in (MODE_FP32, MODE_REMAT):
        return x
    return get_codec(spec.codec).encode(spec, x, key)


def decode(mode: str, enc):
    """Inverse of :func:`encode` (exact or bounded; see error_bound)."""
    spec = parse_spec(mode)
    if spec.codec in (MODE_FP32, MODE_REMAT):
        return enc
    return get_codec(spec.codec).decode(spec, enc)


def quantize(mode: str, x: jax.Array, key: Optional[jax.Array] = None
             ) -> jax.Array:
    """decode(encode(x)) — the fake-quant round trip."""
    spec = parse_spec(mode)
    if spec.codec in (MODE_FP32, MODE_REMAT):
        return x
    return get_codec(spec.codec).quantize(spec, x, key)


def stored_nbytes(mode: str, shape, dtype) -> int:
    """Shape-static bytes the encoding occupies in HBM (capacity)."""
    spec = parse_spec(mode)
    return get_codec(spec.codec).stored_nbytes(spec, shape, dtype)


def capacity_bytes(mode: str, enc) -> int:
    """Static HBM-resident bytes of a concrete encoding."""
    spec = parse_spec(mode)
    if spec.codec in (MODE_FP32, MODE_REMAT):
        return dense_nbytes(enc.shape, enc.dtype)
    return get_codec(spec.codec).capacity_bytes(spec, enc)


def measured_bytes(mode: str, enc) -> jax.Array:
    """Occupancy-aware bytes (traced i32): the wire figure for nsd,
    static capacity for every other codec."""
    spec = parse_spec(mode)
    if spec.codec in (MODE_FP32, MODE_REMAT):
        return jnp.int32(dense_nbytes(enc.shape, enc.dtype))
    return get_codec(spec.codec).measured_bytes(spec, enc)


def error_bound(mode: str, enc):
    """Per-element |decode - x| upper bound, or None when exact."""
    spec = parse_spec(mode)
    if spec.codec in (MODE_FP32, MODE_REMAT):
        return None
    return get_codec(spec.codec).error_bound(spec, enc)


def packed_layout(mode: str, shape, dtype):
    spec = parse_spec(mode)
    return get_codec(spec.codec).packed_layout(spec, shape, dtype)
