"""Logical-axis sharding: the single place where model dims meet mesh axes.

Models annotate every parameter dim and key activations with *logical* axis
names ("embed", "mlp", "q_heads", ...). A ``Rules`` object maps logical names
to mesh axes; conversion checks divisibility and silently falls back to
replication for dims the mesh cannot split (e.g. 40 query heads on a 16-way
model axis) — the fallback is *recorded* so the dry-run can report it.

Rules are installed with a context manager, so model code stays mesh-free
and single-device tests/smoke runs see no sharding machinery at all.
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
from typing import Any, Dict, Optional, Sequence, Tuple, Union

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec

MeshAxes = Union[None, str, Tuple[str, ...]]

# Interconnect class per mesh axis name: collectives over a "dcn" axis
# cross the slow inter-pod network; every other axis rides intra-pod ICI.
# repro.launch.mesh.NodeTopology consults this for axes it doesn't own and
# repro.launch.costmodel prices the two classes at separate bandwidths.
LINK_KINDS = {"pod": "dcn", "pods": "dcn"}


def axis_link_kind(axis_name: str) -> str:
    """"ici" | "dcn" for a mesh axis name (default: ici)."""
    return LINK_KINDS.get(axis_name, "ici")


def auto_axes(mesh: Mesh) -> Mesh:
    """``mesh`` with every axis Auto.

    On an Explicit axis (``jax.make_mesh``'s default) a sharded output
    carries its sharding in its type, and plain indexing such as
    ``means[0]`` must then name an output sharding. Code that builds
    collectives over a caller's mesh normalizes it here, so its outputs
    index like any array whichever mesh the caller made.
    """
    if all(t == AxisType.Auto for t in mesh.axis_types):
        return mesh
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


@dataclasses.dataclass
class Rules:
    """logical axis name -> mesh axis (or tuple of axes, or None)."""

    mapping: Dict[str, MeshAxes]
    mesh: Mesh

    fallbacks: list = dataclasses.field(default_factory=list)

    def _axis_size(self, axes: MeshAxes) -> int:
        if axes is None:
            return 1
        if isinstance(axes, str):
            axes = (axes,)
        size = 1
        for a in axes:
            size *= self.mesh.shape[a]
        return size

    def resolve_dim(self, logical: Optional[str], dim_size: int) -> MeshAxes:
        if logical is None:
            return None
        axes = self.mapping.get(logical)
        if axes is None:
            return None
        n = self._axis_size(axes)
        if n == 1:
            return None
        if dim_size % n != 0:
            self.fallbacks.append((logical, dim_size, axes))
            return None
        return axes

    def pspec(self, logical_axes: Sequence[Optional[str]],
              shape: Sequence[int]) -> PartitionSpec:
        assert len(logical_axes) == len(shape), (logical_axes, shape)
        used: set = set()
        parts = []
        for name, dim in zip(logical_axes, shape):
            axes = self.resolve_dim(name, dim)
            # one mesh axis may shard at most one tensor dim
            flat = (axes,) if isinstance(axes, str) else (axes or ())
            if any(a in used for a in flat):
                parts.append(None)
                continue
            used.update(flat)
            parts.append(axes)
        return PartitionSpec(*parts)

    def sharding(self, logical_axes: Sequence[Optional[str]],
                 shape: Sequence[int]) -> NamedSharding:
        return NamedSharding(self.mesh, self.pspec(logical_axes, shape))


_CURRENT: contextvars.ContextVar[Optional[Rules]] = contextvars.ContextVar(
    "repro_sharding_rules", default=None
)


def current_rules() -> Optional[Rules]:
    return _CURRENT.get()


@contextlib.contextmanager
def use_rules(rules: Optional[Rules]):
    token = _CURRENT.set(rules)
    try:
        yield rules
    finally:
        _CURRENT.reset(token)


def shard_act(x: jax.Array, logical_axes: Sequence[Optional[str]]) -> jax.Array:
    """Sharding-constrain an activation; no-op when no rules installed."""
    rules = current_rules()
    if rules is None:
        return x
    if len(logical_axes) != x.ndim:
        return x
    spec = rules.pspec(logical_axes, x.shape)
    return jax.lax.with_sharding_constraint(x, NamedSharding(rules.mesh, spec))


def spec_tree_to_shardings(specs: Any, rules: Rules, params: Any) -> Any:
    """Convert a logical-axes tree (mirroring params) to NamedShardings."""
    def conv(spec, p):
        shape = p.shape if hasattr(p, "shape") else np.shape(p)
        if spec is None or len(spec) != len(shape):
            # rank mismatch (e.g. scalar master-weight placeholders) -> replicate
            spec = (None,) * len(shape)
        return rules.sharding(spec, shape)

    return jax.tree.map(
        conv, specs, params,
        is_leaf=lambda s: s is None or (isinstance(s, tuple) and all(
            a is None or isinstance(a, str) for a in s)),
    )


# ---------------------------------------------------------------------------
# Standard rule sets (hillclimbing edits these)
# ---------------------------------------------------------------------------

def tp_dp_rules(mesh: Mesh, *, fsdp: bool = False, seq_shard: bool = False,
                data_axes: Tuple[str, ...] = None) -> Rules:
    """Megatron-style TP over "model", DP over ("pod","data").

    fsdp=True additionally shards the non-TP weight dim over "data" (weight-
    gathered on use) — used for big-weight/small-batch decode cells.
    seq_shard=True shards the sequence dim of activations over "model"
    (sequence parallelism for the long-context cells).
    """
    if data_axes is None:
        data_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    mapping: Dict[str, MeshAxes] = {
        # parameters
        "embed": ("data" if fsdp and "data" in mesh.shape else None),
        "vocab": "model",
        "mlp": "model",
        "q_heads": "model",
        "kv_heads": "model",
        "expert": "model",
        "expert_mlp": None,
        "ssm_inner": "model",
        "ssm_state": None,
        "conv_w": None,
        # activations
        "batch": data_axes,
        "seq": ("model" if seq_shard else None),
        "attn_seq": ("model" if seq_shard else None),  # follows seq (It5 refuted decoupling)
        "act_embed": None,
        "act_heads": "model",
        "act_mlp": "model",
        "act_vocab": "model",
        "act_expert": "model",
        "act_ssm_inner": "model",
    }
    return Rules(mapping=mapping, mesh=mesh)
