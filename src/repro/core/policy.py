"""Dither policy — the knob surface for the paper's technique.

The paper has exactly one global hyperparameter: the scale factor ``s`` in
``Delta = s * std(grad)``. Historically this repo carried ``s`` (and the
other numeric knobs) as *static* ``custom_vjp`` arguments, so changing it
meant recompiling every backward matmul. The policy surface is now split in
two along the static/traced line:

* ``StaticSpec`` — the fields that legitimately shape the trace (backward
  variant, telemetry on/off, tag). These stay static arguments of the
  custom_vjp ops; changing them recompiles, which is correct and rare
  (a phase switch in a :class:`repro.core.schedule.PolicyProgram`).
* knobs — the numeric fields (``s``, ``meprop_k_frac``, ``row_alpha``),
  packed into a traced f32 ``(3,)`` array by :func:`knobs_array`. A
  schedule that changes ``s`` every step therefore triggers **zero**
  recompiles (pinned by tests/test_schedule.py).

``DitherPolicy`` remains the user-facing frozen dataclass; its numeric
fields are the *defaults* that get baked into knobs when a
``DitherCtx`` is built. Per-layer / per-step resolution lives in
``repro.core.schedule`` and enters through :meth:`DitherCtx.resolve`.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, NamedTuple, Optional, Set

import jax
import jax.numpy as jnp
import zlib


# Backward-pass variants. "paper" is the faithful baseline; everything else
# is a beyond-paper optimization kept strictly opt-in (see DESIGN.md §2).
VARIANT_OFF = "off"  # plain backprop (the paper's "Baseline" column)
VARIANT_PAPER = "paper"  # NSD on preactivation grads, matmuls in input dtype
VARIANT_INT8 = "int8"  # NSD + int8 MXU backward matmuls (8bit+dither column)
VARIANT_ROW = "row"  # structured row-dither (TPU-native, beyond paper)
VARIANT_MEPROP = "meprop"  # top-k comparator baseline from the paper
VARIANT_KERNEL = "kernel"  # Pallas kernel path: fused NSD + tile-skip matmuls
VARIANTS = (VARIANT_OFF, VARIANT_PAPER, VARIANT_INT8, VARIANT_ROW,
            VARIANT_MEPROP, VARIANT_KERNEL)

# Index layout of the traced knobs array (see knobs_array()).
KNOB_S = 0
KNOB_MEPROP_K_FRAC = 1
KNOB_ROW_ALPHA = 2


def validate_knob_values(s: Any, meprop_k_frac: Any, row_alpha: Any,
                         owner: str) -> None:
    """Shared numeric validation for DitherPolicy / LayerRule fields.

    Only concrete (host-side) values are checked; ``None`` means "not
    overridden" (LayerRule). Schedule-typed fields are validated by their
    owner against every value the schedule can produce
    (``repro.core.schedule``), so a ramp cannot smuggle an illegal knob
    past construction.
    """
    if s is not None and not isinstance(s, jax.Array) and not s > 0:
        raise ValueError(f"{owner}: s must be > 0, got {s!r}")
    if meprop_k_frac is not None and not isinstance(meprop_k_frac, jax.Array) \
            and not 0 < meprop_k_frac <= 1:
        raise ValueError(
            f"{owner}: meprop_k_frac must be in (0, 1], got {meprop_k_frac!r}")
    if row_alpha is not None and not isinstance(row_alpha, jax.Array) \
            and not row_alpha > 0:
        raise ValueError(
            f"{owner}: row_alpha must be > 0, got {row_alpha!r}")


def knobs_array(s, meprop_k_frac, row_alpha) -> jax.Array:
    """Pack the numeric knobs as a traced f32 (3,) vector.

    This is THE boundary between policy configuration and the jitted
    backward pass: everything in here may change per step without
    retracing; everything in StaticSpec may not.
    """
    return jnp.stack([
        jnp.asarray(s, jnp.float32),
        jnp.asarray(meprop_k_frac, jnp.float32),
        jnp.asarray(row_alpha, jnp.float32),
    ])


@dataclasses.dataclass(frozen=True)
class StaticSpec:
    """The trace-shaping part of a resolved per-layer policy.

    Rides through ``jax.custom_vjp`` as a static (hashable) argument;
    deliberately excludes every numeric knob so knob schedules cannot
    invalidate the compile cache. The one exception is
    ``meprop_k_static``: an UNSCHEDULED meprop fraction is carried here so
    the backward keeps the cheap ``lax.top_k(k)`` path (k small) instead
    of the full per-row sort the traced path needs; it is set only for the
    meprop variant, and a scheduled ``meprop_k_frac`` leaves it None
    (traced, zero recompiles).
    """

    variant: str = VARIANT_PAPER
    collect_stats: bool = False
    stats_tag: str = ""
    meprop_k_static: Optional[float] = None
    # residual-memory mode for the layer's saved forward residual (see
    # repro.quant; any registered codec spec): "fp32" is the legacy dense
    # store; "remat"
    # wraps the op in jax.checkpoint; the codecs store x compressed. Static
    # per layer by construction — stamped from MemoryPolicy rules at trace
    # time in DitherCtx.resolve, so knob schedules cannot touch it.
    residual: str = "fp32"
    # registered quant codec spec (repro.quant, e.g. "int4@g32") applied to
    # the pre-activation cotangent INSTEAD of the variant's built-in NSD
    # quantizer; None keeps the variant's own path. Static per layer: codec
    # choice shapes the trace, its parameters live in the spec string.
    grad_codec: Optional[str] = None


class Resolved(NamedTuple):
    """What one layer's contraction gets after policy resolution."""

    spec: StaticSpec  # static: variant + telemetry switches
    knobs: jax.Array  # traced f32 (3,): [s, meprop_k_frac, row_alpha]
    key: jax.Array  # per-(step, layer) dither RNG key


@dataclasses.dataclass(frozen=True)
class DitherPolicy:
    """Per-run configuration of dithered backprop (the global defaults).

    Per-layer / per-step overrides are expressed as a
    :class:`repro.core.schedule.PolicyProgram` on top of this base.
    """

    variant: str = VARIANT_PAPER
    s: float = 2.0  # Delta = s * std(grad); the paper's global knob
    meprop_k_frac: float = 0.1  # fraction of entries kept by the meProp baseline
    row_alpha: float = 1.0  # row-dither aggressiveness (higher -> sparser)
    collect_stats: bool = False  # io_callback telemetry (single-host only)
    exclude: tuple = ()  # layer-name substrings exempted from dithering
    stats_tag: str = ""  # prefix for telemetry records
    # registered quant codec spec for the cotangent (see StaticSpec); None
    # keeps the variant's built-in NSD quantizer
    grad_codec: Optional[str] = None

    def __post_init__(self):
        if self.variant not in VARIANTS:
            raise ValueError(f"unknown variant {self.variant!r}; one of {VARIANTS}")
        validate_knob_values(self.s, self.meprop_k_frac, self.row_alpha,
                             owner="DitherPolicy")
        if self.grad_codec is not None:
            # lazy: repro.quant imports repro.core at module level
            from repro.quant.registry import validate_spec

            validate_spec(self.grad_codec)

    @property
    def enabled(self) -> bool:
        return self.variant != VARIANT_OFF

    def applies_to(self, name: str) -> bool:
        if not self.enabled:
            return False
        return not any(pat in name for pat in self.exclude)

    def replace(self, **kw) -> "DitherPolicy":
        return dataclasses.replace(self, **kw)

    def spec(self) -> StaticSpec:
        return StaticSpec(variant=self.variant,
                          collect_stats=self.collect_stats,
                          stats_tag=self.stats_tag,
                          meprop_k_static=(self.meprop_k_frac
                                           if self.variant == VARIANT_MEPROP
                                           else None),
                          grad_codec=self.grad_codec)

    def knobs(self) -> jax.Array:
        return knobs_array(self.s, self.meprop_k_frac, self.row_alpha)


# A do-nothing policy: models built with ctx=None or this policy run plain
# backprop, which keeps inference/serving traces free of custom_vjp machinery.
OFF = DitherPolicy(variant=VARIANT_OFF)


def name_salt(name: str) -> int:
    """Stable 31-bit salt for folding a layer name into the step RNG key."""
    return zlib.crc32(name.encode()) & 0x7FFFFFFF


@dataclasses.dataclass
class DitherCtx:
    """Threaded through model ``apply`` — step RNG + policy resolution.

    ``key`` must differ per optimization step (fold the step index in); each
    layer folds its own name in so dither noise is i.i.d. across layers,
    steps, and (via the caller folding in a worker id) data-parallel workers,
    which is what makes the distributed averaging argument of paper §3.6 hold.

    ``policy`` is the phase-resolved static base (see
    ``PolicyProgram.phase_policy_at``); when ``program`` is set, per-layer
    resolution (rules, knob schedules, controller scales) happens in
    :meth:`resolve` at trace time — layer names are static strings, so
    resolution costs nothing at run time and the resulting knobs are traced
    scalars (changing them never recompiles).
    """

    key: jax.Array
    policy: DitherPolicy = dataclasses.field(default_factory=DitherPolicy)
    # static PolicyProgram (repro.core.schedule); None = plain global policy
    program: Any = None
    # traced i32 step for knob schedules; None behaves as step 0
    step: Optional[jax.Array] = None
    # traced per-layer log-scale on s from the closed-loop sparsity
    # controller: {layer_name: f32 scalar}; rides the checkpoint tree
    ctrl: Optional[Dict[str, jax.Array]] = None
    # trace-time layer-name recorder (schedule.discover_layer_names)
    recorder: Optional[Set[str]] = None
    # static repro.memory.MemoryPolicy selecting the residual codec (or
    # remat) per layer name; None = legacy dense fp32 residuals
    memory: Any = None
    # trace-time residual-footprint recorder: {name: (stored, dense) bytes}
    # (repro.memory.accounting.residual_report)
    mem_recorder: Optional[Dict[str, tuple]] = None
    # {layer name: zero f32 (4,)}; each one's cotangent counts that layer's
    # kernel-path work (repro.core.dithered.TALLY_FIELDS); None = nothing
    # counted
    tally: Optional[Dict[str, jax.Array]] = None

    def key_for(self, name: str) -> jax.Array:
        return jax.random.fold_in(self.key, name_salt(name))

    def resolve(self, name: str) -> Optional[Resolved]:
        """Per-layer policy resolution; None = run plain backprop."""
        if self.recorder is not None:
            self.recorder.add(name)
        if self.program is not None:
            r = self.program.resolve_layer(self, name)
        elif not self.policy.applies_to(name):
            r = None
        else:
            r = Resolved(spec=self.policy.spec(), knobs=self.policy.knobs(),
                         key=self.key_for(name))
        # residual-memory resolution is centralized here so the plain-policy
        # and program paths cannot diverge; the mode lands in the STATIC
        # spec, never in the traced knobs.
        if r is not None and self.memory is not None:
            mode = self.memory.mode_for(name)
            if mode != r.spec.residual:
                r = Resolved(
                    spec=dataclasses.replace(r.spec, residual=mode),
                    knobs=r.knobs, key=r.key)
        return r

    def with_key(self, key: jax.Array) -> "DitherCtx":
        """Same resolution state, different RNG stream (micro-batches,
        shard_map bodies)."""
        return dataclasses.replace(self, key=key)

    @staticmethod
    def for_step(base_key: jax.Array, step, policy: DitherPolicy,
                 worker: int | jax.Array = 0, *, program: Any = None,
                 ctrl: Optional[Dict[str, jax.Array]] = None,
                 memory: Any = None) -> "DitherCtx":
        k = jax.random.fold_in(base_key, step)
        k = jax.random.fold_in(k, worker)
        return DitherCtx(key=k, policy=policy, program=program,
                         step=jnp.asarray(step, jnp.int32), ctrl=ctrl,
                         memory=memory)
