"""Compressed ring all-reduce: NSD gradients cross every hop in wire format.

The classic ring all-reduce moves 2*(N-1)/N of the gradient over each link
as dense f32. Here every hop carries the packed NSD representation instead:

  reduce-scatter   N-1 hops; each node adds its contribution to the partial
                   sum of one segment and RE-DITHERS it (a fresh NSD pack
                   with a per-(hop, node) key) before forwarding — the wire
                   never sees a dense partial sum, and because NSD noise is
                   zero-mean and i.i.d. across hops the re-quantization
                   errors average out rather than accumulate in expectation.
  all-gather       each completed segment is packed ONCE by its owner and
                   forwarded verbatim N-1 times (no reduction -> no repack).

Error accounting (paper eq. 5/6 + pointwise |Q(x) - x| <= Delta): segment c
is packed N-1 times during reduce-scatter and once at gather, so

    |result - dense_mean|  <=  (sum of those N packs' Deltas) / N

pointwise. ``RingTelemetry.error_bound`` reports that bound, measured from
the actual per-hop Deltas; tests assert against it. Wire bytes are measured
per pack (bitmap + non-zero levels), never estimated. The segmenting, hop-
key, and accounting helpers are shared with the two-level reduce in
``repro.comm.hierarchy`` via ``repro.comm.reduce_base`` — which also cuts
the flat ring's N sequential packs per segment down to
(P-1) + ceil(log2 G) + 1 when the node set spans pods (see that module).

Two implementations with identical per-hop math:

  * ``ring_allreduce_nsd`` — single-process simulation (a Python loop over
    nodes/hops). Runs anywhere, including the CPU test container; this is
    what the benchmarks and ``repro.distributed`` use by default.
  * ``make_ring_allreduce`` — the real thing: a ``shard_map`` program whose
    hops are ``jax.lax.ppermute`` of the PackedNSD pytree, so compressed
    bytes are what crosses the device boundary. Exercised under
    ``--xla_force_host_platform_device_count`` in tests/test_comm.py.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import List, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.quant import wire as wf
from repro.comm.reduce_base import (PackCounter, ReduceTelemetry, hop_key,
                                    pack_hop, seg_len, segment)
from repro.parallel.axes import auto_axes

_REDUCE_SALT = 0x51D5
_GATHER_SALT = 0xA11C

# Back-compat alias: the ring predates the shared base module.
RingTelemetry = ReduceTelemetry


@dataclasses.dataclass(frozen=True)
class RingConfig:
    s: float = 1.0  # NSD scale for on-wire quantization
    chunk: int = wf.DEFAULT_CHUNK


def dense_reduce_bytes(size: int, n: int, chunk: int = wf.DEFAULT_CHUNK
                       ) -> int:
    """Bytes the same N-node ring exchange would move at dense f32."""
    return 2 * n * (n - 1) * seg_len(size, n, chunk) * 4


def ring_allreduce_nsd(grads: Union[jax.Array, Sequence[jax.Array]],
                       key: jax.Array, cfg: RingConfig = RingConfig()
                       ) -> Tuple[jax.Array, RingTelemetry]:
    """Simulated compressed ring all-reduce of N stacked node gradients.

    grads: (N, *shape) stacked array or list of N same-shape arrays.
    Returns (mean over nodes, telemetry). N == 1 short-circuits (no wire).
    """
    if not isinstance(grads, jax.Array):
        grads = jnp.stack(list(grads))
    n = grads.shape[0]
    shape, dtype = grads.shape[1:], grads.dtype
    if n == 1:
        zero = jnp.float32(0.0)
        return grads[0], RingTelemetry(zero, zero, zero, 0, 0)

    flat = grads.astype(jnp.float32).reshape(n, -1)
    segs_per_node = []
    for i in range(n):
        segs, _ = segment(flat[i], n, cfg.chunk)
        segs_per_node.append(segs)
    # acc[i][c]: node i's current value for ring segment c
    acc: List[jax.Array] = list(segs_per_node)

    ctr = PackCounter(n)

    # --- reduce-scatter: segment c travels c -> c+1 -> ... -> c-1 ---
    for step in range(n - 1):
        packed = []
        for i in range(n):
            c = (i - step) % n
            p = pack_hop(acc[i][c], hop_key(key, _REDUCE_SALT, step, i),
                         cfg.s, cfg.chunk)
            packed.append((c, p))
            ctr.count(p, seg=c)
        for i in range(n):
            c, p = packed[i]
            j = (i + 1) % n
            acc[j] = acc[j].at[c].set(acc[j][c] + wf.unpack_nsd(p))

    # --- all-gather: owner (c-1) % n packs segment c once, forwards N-1x ---
    gathered = []
    for c in range(n):
        owner = (c - 1) % n
        p = pack_hop(acc[owner][c], hop_key(key, _GATHER_SALT, c, 0),
                     cfg.s, cfg.chunk)
        ctr.count(p, seg=c, hops=n - 1)
        gathered.append(wf.unpack_nsd(p))

    total = jnp.concatenate(gathered)
    size = 1
    for d in shape:
        size *= int(d)
    mean = (total[:size] / n).reshape(shape).astype(dtype)

    n_hops = n * (n - 1) * 2
    dense = jnp.float32(dense_reduce_bytes(flat.shape[1], n, cfg.chunk))
    return mean, RingTelemetry(wire_bytes=ctr.wire_total, dense_bytes=dense,
                               error_bound=jnp.max(ctr.bound) / n,
                               n_hops=n_hops, packs_per_segment=n)


def make_ring_allreduce(mesh: Mesh, axis_name: str,
                        cfg: RingConfig = RingConfig()):
    """Build the shard_map compressed ring all-reduce over ``axis_name``.

    Returns ``fn(stacked) -> (mean, wire_bytes)`` where ``stacked`` is
    (N, *shape) sharded over the mesh axis; every hop moves a PackedNSD
    pytree between neighboring devices via ``jax.lax.ppermute``.
    """
    n = mesh.shape[axis_name]
    fwd = [(i, (i + 1) % n) for i in range(n)]

    def ring(stacked_local: jax.Array, key: jax.Array):
        local = stacked_local[0]  # (1, *shape) local slice of the stack
        me = jax.lax.axis_index(axis_name)
        shape, dtype = local.shape, local.dtype
        acc, seg = segment(local.astype(jnp.float32).reshape(-1),
                           n, cfg.chunk)
        ctr = PackCounter(n)  # deltas of packs THIS node sent

        perm = partial(jax.lax.ppermute, axis_name=axis_name, perm=fwd)

        for step in range(n - 1):
            c_send = (me - step) % n
            p = pack_hop(jnp.take(acc, c_send, axis=0),
                         hop_key(key, _REDUCE_SALT, step, me),
                         cfg.s, cfg.chunk)
            ctr.count(p, seg=c_send)
            p_in = perm(p)
            c_recv = (me - 1 - step) % n
            acc = acc.at[c_recv].set(
                jnp.take(acc, c_recv, axis=0) + wf.unpack_nsd(p_in))

        c_own = (me + 1) % n  # node m finished segment m+1
        p = pack_hop(jnp.take(acc, c_own, axis=0),
                     hop_key(key, _GATHER_SALT, c_own, 0),
                     cfg.s, cfg.chunk)
        ctr.count(p, seg=c_own, hops=0)  # charge the Delta; bytes per hop
        out = jnp.zeros_like(acc).at[c_own].set(wf.unpack_nsd(p))
        cur = p
        for h in range(1, n):
            cur = perm(cur)
            ctr.count(cur)
            c = (me - h + 1) % n
            out = out.at[c].set(wf.unpack_nsd(cur))

        # per-segment bound = sum over ALL senders that touched the segment
        bound = jax.lax.psum(ctr.bound, axis_name)
        size = 1
        for d in shape:
            size *= int(d)
        mean = (out.reshape(-1)[:size] / n).reshape(shape).astype(dtype)
        return mean[None], ctr.wire_total[None], (jnp.max(bound) / n)[None]

    return jax.jit(jax.shard_map(
        ring, mesh=auto_axes(mesh),
        in_specs=(P(axis_name), P()),
        out_specs=(P(axis_name), P(axis_name), P(axis_name))))


def allreduce_compressed(grads, key, cfg=RingConfig(), mesh: Mesh = None,
                         axis_name: str = "nodes", pod_axis: str = "pods"):
    """Deprecated: dispatch reduces through ``repro.comm.reducer`` instead.

    Kept as a thin shim over the same internals the reducer uses — bit-
    identical results, pinned by tests/test_reducer.py.

    ``cfg`` selects the topology: a ``RingConfig`` runs the flat ring, a
    ``repro.comm.hierarchy.HierConfig`` the two-level (intra-pod ring +
    inter-pod tree) reduce. With a multi-device ``mesh`` the shard_map
    implementation runs (the hierarchy needs a 2-D (pod_axis, axis_name)
    mesh); otherwise the single-process simulation with identical per-hop
    math.
    """
    import warnings

    from repro.comm import hierarchy as hier  # local: avoid import cycle

    warnings.warn(
        "allreduce_compressed is deprecated; use repro.comm.reducer("
        "policy, mesh) which owns topology dispatch and telemetry",
        DeprecationWarning, stacklevel=2)
    if isinstance(cfg, hier.HierConfig):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            return hier.allreduce_hier(grads, key, cfg, mesh=mesh,
                                       pod_axis=pod_axis,
                                       node_axis=axis_name)
    if mesh is not None and mesh.shape[axis_name] > 1:
        if not isinstance(grads, jax.Array):
            grads = jnp.stack(list(grads))
        n = mesh.shape[axis_name]
        if grads.shape[0] != n:
            raise ValueError(
                f"stacked node axis ({grads.shape[0]}) must equal the mesh "
                f"{axis_name!r} axis size ({n}); a mismatched stack would "
                "silently drop gradients")
        fn = make_ring_allreduce(mesh, axis_name, cfg)
        means, wires, bounds = fn(grads, key)
        flat_size = 1
        for d in grads.shape[1:]:
            flat_size *= int(d)
        n_hops = 2 * n * (n - 1)
        tele = RingTelemetry(
            wire_bytes=jnp.sum(wires),
            dense_bytes=jnp.float32(
                dense_reduce_bytes(flat_size, n, cfg.chunk)),
            error_bound=bounds[0], n_hops=n_hops, packs_per_segment=n)
        return means[0], tele
    return ring_allreduce_nsd(grads, key, cfg)
