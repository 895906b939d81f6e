"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before any jax initialization).

Target hardware: TPU v5e pods — 256 chips/pod, (16, 16) 2D slice per pod;
multi-pod adds a leading "pod" axis over DCN. Per-chip constants used by the
roofline harness live in repro.launch.roofline.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
from jax.sharding import AxisType, Mesh

from repro.parallel.axes import axis_link_kind


def _auto_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    """``jax.make_mesh`` with every axis Auto: the compiler propagates
    shardings, and code may index a sharded array without naming the
    output sharding (``jax.make_mesh``'s own default is Explicit)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    """Arbitrary mesh (elastic re-mesh after failures uses this)."""
    return _auto_mesh(shape, axes)


def host_device_mesh(n_model: int = 1, n_data: Optional[int] = None) -> Mesh:
    """Mesh over however many (host) devices exist — used by tests."""
    n = jax.device_count()
    if n_data is None:
        n_data = n // n_model
    return make_mesh((n_data, n_model), ("data", "model"))


@dataclasses.dataclass(frozen=True)
class NodeTopology:
    """Physical layout of the data-parallel node set: pods x nodes-per-pod.

    The descriptor the comm subsystem plans its reduce around: collectives
    over ``node_axis`` ride the fast intra-pod interconnect (ICI),
    collectives over ``pod_axis`` cross the slow inter-pod network (DCN).
    ``repro.comm.hierarchy`` reduces over the two axes separately;
    ``repro.launch.costmodel.price_reduce`` prices each axis at its own
    bandwidth. ``flat()`` describes a single-pod (pure-ring) layout.
    """

    pods: int = 1
    nodes_per_pod: int = 1
    pod_axis: str = "pods"
    node_axis: str = "nodes"

    def __post_init__(self):
        if self.pods < 1 or self.nodes_per_pod < 1:
            raise ValueError(f"degenerate topology {self}")

    @classmethod
    def flat(cls, n_nodes: int) -> "NodeTopology":
        return cls(pods=1, nodes_per_pod=n_nodes)

    @property
    def n_nodes(self) -> int:
        return self.pods * self.nodes_per_pod

    def link_kind(self, axis_name: str) -> str:
        """"dcn" for the pod axis, else the generic axis registry."""
        if axis_name == self.pod_axis:
            return "dcn"
        if axis_name == self.node_axis:
            return "ici"
        return axis_link_kind(axis_name)

    def mesh(self) -> Mesh:
        """Build the mesh this topology describes (2-D unless single-pod)."""
        if self.pods == 1:
            return _auto_mesh((self.nodes_per_pod,), (self.node_axis,))
        return _auto_mesh((self.pods, self.nodes_per_pod),
                          (self.pod_axis, self.node_axis))


def make_node_mesh(topo: NodeTopology) -> Mesh:
    """Mesh for a data-parallel node set laid out per ``topo``."""
    return topo.mesh()
