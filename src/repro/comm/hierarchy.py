"""Two-level compressed all-reduce: intra-pod NSD ring + inter-pod tree.

The flat ring in ``repro.comm.ring`` re-dithers each segment N-1 times, so
its sequential pack depth — and with it the pointwise error bound — grows
linearly with node count. Real pod-scale deployments are not flat: nodes
inside a pod share a fast ICI axis while pods talk over a much slower DCN
axis. This module reduces over that hierarchy instead, for N = G pods of
P nodes each:

  phase 1  intra-pod ring reduce-scatter: P-1 hops over ICI, re-dithered
           per hop exactly like the flat ring. Node (g, p) ends up owning
           segment c = (p+1) mod P of pod g's partial sum.
  phase 2  inter-pod binomial-tree reduce: ceil(log2 G) rounds over DCN.
           Each segment's per-pod owner acts as that segment's pod leader:
           in round r the owner in pod g with g mod 2^(r+1) == 2^r packs
           its partial (fresh per-(round, pod, segment) key) and sends it
           to pod g - 2^r, which unpacks and accumulates. Non-power-of-two
           pod counts just skip absent partners.
  phase 3  the root pod's owner packs the finished global segment ONCE;
           that pack is forwarded VERBATIM back down the tree (G-1 hops
           over DCN, no repack) ...
  phase 4  ... and around each pod's ring (P-1 hops per pod over ICI, no
           repack), so every node reconstructs the identical value.

Pack/error accounting (paper eq. 5/6, |Q(x) - x| <= Delta pointwise): a
segment crosses only

    (P-1) + ceil(log2 G) + 1   sequential packs   (flat ring: N)

and its final value absorbs the Deltas of G*(P-1) intra packs + (G-1)
tree packs + 1 broadcast pack = N packs total — the same COUNT as the
flat ring's N, but each intra/tree pack quantizes a pod-sized partial sum
(std ~ sqrt(P), sqrt(2^r P)) instead of the flat ring's ever-growing
global partial (std up to ~ sqrt(N)), so the summed Deltas — and the
reported ``error_bound`` — are strictly tighter on the same input.
Telemetry splits measured wire bytes by link class (ICI vs DCN) so
``repro.launch.costmodel`` can price the two axes separately and show when
the tree wins.

Two implementations with identical per-hop math and identical keys:

  * ``hier_allreduce_nsd`` — single-process simulation (Python loops).
  * ``make_hier_allreduce`` — shard_map over a 2-D (pods, nodes) mesh;
    every hop is a ``jax.lax.ppermute`` of the PackedNSD pytree (over the
    node axis for ICI hops, the pod axis for DCN hops). Exercised under
    ``--xla_force_host_platform_device_count`` in tests/test_hierarchy.py,
    including a non-power-of-two pod count.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.quant import wire as wf
from repro.comm.reduce_base import (PackCounter, hop_key, pack_hop, seg_len,
                                    segment)
from repro.parallel.axes import auto_axes

_INTRA_SALT = 0x1C1A  # intra-pod ring reduce-scatter packs
_TREE_UP_SALT = 0x7EE0  # inter-pod tree-reduce packs
_TREE_DOWN_SALT = 0xB0AD  # the single broadcast pack per segment


def tree_rounds(pods: int) -> int:
    """ceil(log2(pods)): rounds of the binomial tree over the pod axis."""
    return (pods - 1).bit_length() if pods > 1 else 0


@dataclasses.dataclass(frozen=True)
class HierConfig:
    """Two-level reduce configuration: N nodes = pods x (N // pods)."""

    pods: int = 2
    s: float = 1.0  # NSD scale for on-wire quantization
    chunk: int = wf.DEFAULT_CHUNK

    def __post_init__(self):
        if self.pods < 1:
            raise ValueError(f"pods must be >= 1, got {self.pods}")


class HierTelemetry(NamedTuple):
    """ReduceTelemetry plus the per-link-class split the cost model needs."""

    wire_bytes: jax.Array  # f32 scalar: total bytes crossing all links
    dense_bytes: jax.Array  # f32 scalar: same exchange at dense f32
    error_bound: jax.Array  # f32 scalar: max pointwise |result - mean| bound
    n_hops: int  # static: total link traversals (both classes)
    packs_per_segment: int  # static: sequential re-quantizations
    wire_ici_bytes: jax.Array  # f32 scalar: intra-pod (fast axis) bytes
    wire_dcn_bytes: jax.Array  # f32 scalar: inter-pod (slow axis) bytes
    pods: int = 1  # static: G
    per_pod: int = 1  # static: P
    # f32 scalar: max over pods of DCN bytes through that pod's slow-axis
    # link (sent + received) — the busiest-line occupancy the butterfly
    # variant (repro.comm.butterfly) is designed to cut. 0.0 where the
    # path doesn't track it (shard_map dispatch, zero telemetry).
    peak_dcn_bytes: Union[jax.Array, float] = 0.0

    @property
    def ratio(self) -> jax.Array:
        return self.wire_bytes / jnp.maximum(self.dense_bytes, 1.0)


def _hier_shape(n: int, pods: int) -> Tuple[int, int]:
    if n % pods != 0:
        raise ValueError(
            f"node count ({n}) must be divisible by the pod count ({pods}); "
            "ragged pods would leave some gradients out of the reduce")
    return pods, n // pods


def _mesh_axes(mesh: Mesh, pod_axis: str, node_axis: str) -> Tuple[int, int]:
    """Validate the 2-D (pod, node) mesh precondition with a real error."""
    missing = [a for a in (pod_axis, node_axis) if a not in mesh.shape]
    if missing:
        raise ValueError(
            f"hierarchical reduce needs a 2-D ({pod_axis!r}, {node_axis!r}) "
            f"mesh; this mesh has axes {tuple(mesh.shape)} (missing "
            f"{missing}) — build one with launch.mesh.make_node_mesh("
            f"NodeTopology(pods=..., nodes_per_pod=...))")
    return mesh.shape[pod_axis], mesh.shape[node_axis]


def _zero_telemetry() -> HierTelemetry:
    zero = jnp.float32(0.0)
    return HierTelemetry(zero, zero, zero, 0, 0, zero, zero, 1, 1)


def _hop_counts(g: int, p: int) -> Tuple[int, int]:
    """(ici segment-hops, dcn segment-hops) of the whole exchange."""
    ici = 2 * g * p * (p - 1)  # reduce-scatter + gather forwarding
    dcn = 2 * p * (g - 1)  # tree up + tree down, per segment owner line
    return ici, dcn


def dense_reduce_bytes(size: int, pods: int, per_pod: int,
                       chunk: int = wf.DEFAULT_CHUNK) -> int:
    """Bytes the same two-level exchange would move at dense f32."""
    ici, dcn = _hop_counts(pods, per_pod)
    return (ici + dcn) * seg_len(size, per_pod, chunk) * 4


def hier_allreduce_nsd(grads: Union[jax.Array, Sequence[jax.Array]],
                       key: jax.Array, cfg: HierConfig = HierConfig()
                       ) -> Tuple[jax.Array, HierTelemetry]:
    """Simulated two-level compressed all-reduce of N stacked gradients.

    grads: (N, *shape) stacked array or list of N same-shape arrays, pod-
    major (node i lives in pod i // per_pod). Returns (mean over nodes,
    telemetry). N == 1 short-circuits (no wire).
    """
    if not isinstance(grads, jax.Array):
        grads = jnp.stack(list(grads))
    n = grads.shape[0]
    shape, dtype = grads.shape[1:], grads.dtype
    if n == 1:
        return grads[0], _zero_telemetry()
    G, Pn = _hier_shape(n, cfg.pods)

    flat = grads.astype(jnp.float32).reshape(n, -1)
    # acc[g][p]: (Pn, seg) — node (g, p)'s current view of its pod's segments
    acc = [[segment(flat[g * Pn + p], Pn, cfg.chunk)[0] for p in range(Pn)]
           for g in range(G)]
    ctr = PackCounter(Pn)

    # --- phase 1: intra-pod ring reduce-scatter (re-dither per hop) ---
    for step in range(Pn - 1):
        packed = []
        for g in range(G):
            for p in range(Pn):
                c = (p - step) % Pn
                pk = pack_hop(acc[g][p][c],
                              hop_key(key, _INTRA_SALT, step, g, p),
                              cfg.s, cfg.chunk)
                ctr.count(pk, seg=c, link="ici")
                packed.append((g, p, c, pk))
        for g, p, c, pk in packed:
            dst = (p + 1) % Pn
            acc[g][dst] = acc[g][dst].at[c].set(
                acc[g][dst][c] + wf.unpack_nsd(pk))

    # partial[g][c]: pod g's sum of segment c, held by owner (c-1) % Pn
    part = [[acc[g][(c - 1) % Pn][c] for c in range(Pn)] for g in range(G)]

    # per-pod DCN line traffic (sent + received) for the peak-occupancy
    # telemetry the butterfly variant gates against
    traffic = [jnp.float32(0.0) for _ in range(G)]

    # --- phase 2: inter-pod binomial tree reduce (re-pack per combine) ---
    rounds = tree_rounds(G)
    for r in range(rounds):
        stride = 1 << r
        for g in range(G):
            if g % (2 * stride) != stride:
                continue
            dst = g - stride
            for c in range(Pn):
                pk = pack_hop(part[g][c],
                              hop_key(key, _TREE_UP_SALT, r, g, c),
                              cfg.s, cfg.chunk)
                ctr.count(pk, seg=c, link="dcn")
                b = pk.wire_bytes().astype(jnp.float32)
                traffic[g] = traffic[g] + b
                traffic[dst] = traffic[dst] + b
                part[dst][c] = part[dst][c] + wf.unpack_nsd(pk)

    # --- phase 3+4: root packs once; forwarded verbatim down the tree
    # (G-1 DCN hops) then around each pod's ring (P-1 ICI hops per pod) ---
    finals = []
    for c in range(Pn):
        pk = pack_hop(part[0][c], hop_key(key, _TREE_DOWN_SALT, 0, 0, c),
                      cfg.s, cfg.chunk)
        ctr.count(pk, seg=c, link="dcn", hops=G - 1)
        ctr.count(pk, link="ici", hops=G * (Pn - 1))
        b = pk.wire_bytes().astype(jnp.float32)
        for r in range(rounds - 1, -1, -1):
            stride = 1 << r
            for src in range(0, G, 2 * stride):
                if src + stride < G:
                    traffic[src] = traffic[src] + b
                    traffic[src + stride] = traffic[src + stride] + b
        finals.append(wf.unpack_nsd(pk))

    total = jnp.concatenate(finals)
    size = 1
    for d in shape:
        size *= int(d)
    mean = (total[:size] / n).reshape(shape).astype(dtype)

    ici_hops, dcn_hops = _hop_counts(G, Pn)
    dense = jnp.float32(dense_reduce_bytes(flat.shape[1], G, Pn, cfg.chunk))
    return mean, HierTelemetry(
        wire_bytes=ctr.wire_total, dense_bytes=dense,
        error_bound=jnp.max(ctr.bound) / n, n_hops=ici_hops + dcn_hops,
        packs_per_segment=(Pn - 1) + rounds + 1,
        wire_ici_bytes=ctr.wire["ici"], wire_dcn_bytes=ctr.wire["dcn"],
        pods=G, per_pod=Pn,
        peak_dcn_bytes=(jnp.max(jnp.stack(traffic)) if G > 1
                        else jnp.float32(0.0)))


def make_hier_allreduce(mesh: Mesh, cfg: HierConfig = HierConfig(),
                        pod_axis: str = "pods", node_axis: str = "nodes"):
    """Deprecated: build reduces through ``repro.comm.reducer`` instead.

    Thin shim over the internal builder the reducer consumes; results are
    bit-identical (pinned by tests/test_reducer.py)."""
    import warnings
    warnings.warn(
        "make_hier_allreduce is deprecated; use repro.comm.reducer("
        "policy, mesh) which owns topology dispatch and telemetry",
        DeprecationWarning, stacklevel=2)
    return _make_hier_allreduce(mesh, cfg, pod_axis, node_axis)


def _make_hier_allreduce(mesh: Mesh, cfg: HierConfig = HierConfig(),
                         pod_axis: str = "pods", node_axis: str = "nodes"):
    """Build the shard_map two-level reduce over a 2-D (pods, nodes) mesh.

    Returns ``fn(stacked, key) -> (means, wire_ici, wire_dcn, bounds)``
    with ``stacked`` (N, *shape) pod-major over the flattened mesh; every
    ICI hop is a ppermute over ``node_axis``, every DCN hop a ppermute
    over ``pod_axis``. Per-hop math and keys match ``hier_allreduce_nsd``
    bit-exactly.
    """
    G, Pn = _mesh_axes(mesh, pod_axis, node_axis)
    if cfg.pods != G:
        raise ValueError(f"cfg.pods ({cfg.pods}) != mesh {pod_axis!r} axis "
                         f"size ({G})")
    rounds = tree_rounds(G)
    fwd_nodes = [(i, (i + 1) % Pn) for i in range(Pn)]

    def hier(stacked_local: jax.Array, key: jax.Array):
        local = stacked_local[0]  # (1, *shape) local slice of the stack
        g = jax.lax.axis_index(pod_axis)
        me = jax.lax.axis_index(node_axis)
        shape, dtype = local.shape, local.dtype
        acc, seg = segment(local.astype(jnp.float32).reshape(-1),
                           Pn, cfg.chunk)
        ctr = PackCounter(Pn)
        perm_n = partial(jax.lax.ppermute, axis_name=node_axis,
                         perm=fwd_nodes)

        # --- phase 1: intra-pod ring reduce-scatter over the node axis ---
        for step in range(Pn - 1):
            c_send = (me - step) % Pn
            pk = pack_hop(jnp.take(acc, c_send, axis=0),
                          hop_key(key, _INTRA_SALT, step, g, me),
                          cfg.s, cfg.chunk)
            ctr.count(pk, seg=c_send, link="ici")
            pk_in = perm_n(pk)
            c_recv = (me - 1 - step) % Pn
            acc = acc.at[c_recv].set(
                jnp.take(acc, c_recv, axis=0) + wf.unpack_nsd(pk_in))

        c_own = (me + 1) % Pn  # this node owns its pod's sum of c_own
        part = jnp.take(acc, c_own, axis=0)

        # --- phase 2: binomial tree over the pod axis (SPMD: every device
        # packs, but only actual senders' packs count and cross the wire;
        # non-receivers get an all-zero pack from ppermute -> add 0) ---
        for r in range(rounds):
            stride = 1 << r
            is_sender = (g % (2 * stride)) == stride
            pk = pack_hop(part, hop_key(key, _TREE_UP_SALT, r, g, c_own),
                          cfg.s, cfg.chunk)
            ctr.count(pk, seg=c_own, link="dcn", weight=is_sender)
            perm = [(src, src - stride) for src in range(G)
                    if src % (2 * stride) == stride]
            pk_in = jax.lax.ppermute(pk, axis_name=pod_axis, perm=perm)
            part = part + wf.unpack_nsd(pk_in)

        # --- phase 3: pod 0's owner packs the global segment once, then
        # the pack travels down the tree verbatim (receivers ADOPT it) ---
        pk = pack_hop(part, hop_key(key, _TREE_DOWN_SALT, 0, 0, c_own),
                      cfg.s, cfg.chunk)
        is_root = (g == 0)
        ctr.count(pk, seg=c_own, link="dcn", hops=0, weight=is_root)
        for r in range(rounds - 1, -1, -1):
            stride = 1 << r
            # holders after round r+1 are pods == 0 mod 2*stride
            is_sender = ((g % (2 * stride)) == 0) & (g + stride < G)
            ctr.count(pk, link="dcn", weight=is_sender)
            perm = [(src, src + stride) for src in range(0, G, 2 * stride)
                    if src + stride < G]
            pk_in = jax.lax.ppermute(pk, axis_name=pod_axis, perm=perm)
            is_recv = (g % (2 * stride)) == stride
            pk = jax.tree.map(lambda a, b: jnp.where(is_recv, b, a),
                              pk, pk_in)

        # --- phase 4: forward the final pack around the pod ring ---
        out = jnp.zeros_like(acc).at[c_own].set(wf.unpack_nsd(pk))
        cur = pk
        for h in range(1, Pn):
            cur = perm_n(cur)
            ctr.count(cur, link="ici")
            c = (me - h + 1) % Pn
            out = out.at[c].set(wf.unpack_nsd(cur))

        # per-segment bound = sum over ALL packs that touched the segment
        bound = jax.lax.psum(ctr.bound, (pod_axis, node_axis))
        size = 1
        for d in shape:
            size *= int(d)
        n = G * Pn
        mean = (out.reshape(-1)[:size] / n).reshape(shape).astype(dtype)
        return (mean[None], ctr.wire["ici"][None], ctr.wire["dcn"][None],
                (jnp.max(bound) / n)[None])

    spec = P((pod_axis, node_axis))
    return jax.jit(jax.shard_map(
        hier, mesh=auto_axes(mesh),
        in_specs=(spec, P()),
        out_specs=(spec, spec, spec, spec)))


def allreduce_hier(grads, key, cfg: HierConfig = HierConfig(),
                   mesh: Mesh = None, pod_axis: str = "pods",
                   node_axis: str = "nodes"
                   ) -> Tuple[jax.Array, HierTelemetry]:
    """Deprecated: dispatch reduces through ``repro.comm.reducer`` instead.

    Shard_map two-level reduce when a 2-D multi-device mesh is given, else
    the single-process simulation (identical per-hop math). Kept as a thin
    shim over the same internals the reducer uses — bit-identical results,
    pinned by tests/test_reducer.py."""
    import warnings
    warnings.warn(
        "allreduce_hier is deprecated; use repro.comm.reducer(policy, "
        "mesh) which owns topology dispatch and telemetry",
        DeprecationWarning, stacklevel=2)
    if not isinstance(grads, jax.Array):
        grads = jnp.stack(list(grads))
    n = grads.shape[0]
    if mesh is not None and n > 1:
        G, Pn = _mesh_axes(mesh, pod_axis, node_axis)
        if grads.shape[0] != G * Pn:
            raise ValueError(
                f"stacked node axis ({grads.shape[0]}) must equal the mesh "
                f"({pod_axis!r} x {node_axis!r}) size ({G}*{Pn}); a "
                "mismatched stack would silently drop gradients")
        fn = _make_hier_allreduce(mesh, cfg, pod_axis, node_axis)
        means, w_ici, w_dcn, bounds = fn(grads, key)
        flat_size = 1
        for d in grads.shape[1:]:
            flat_size *= int(d)
        ici_hops, dcn_hops = _hop_counts(G, Pn)
        wire_ici = jnp.sum(w_ici)
        wire_dcn = jnp.sum(w_dcn)
        tele = HierTelemetry(
            wire_bytes=wire_ici + wire_dcn,
            dense_bytes=jnp.float32(
                dense_reduce_bytes(flat_size, G, Pn, cfg.chunk)),
            error_bound=bounds[0], n_hops=ici_hops + dcn_hops,
            packs_per_segment=(Pn - 1) + tree_rounds(G) + 1,
            wire_ici_bytes=wire_ici, wire_dcn_bytes=wire_dcn,
            pods=G, per_pod=Pn)
        return means[0], tele
    return hier_allreduce_nsd(grads, key, cfg)
