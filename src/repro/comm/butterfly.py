"""Butterfly (recursive-halving) inter-pod stage for the two-level reduce.

The binomial tree in ``repro.comm.hierarchy`` funnels every segment through
pod 0: the root's DCN line carries ceil(log2 G) full-segment packs up AND
the broadcast pack down, so its occupancy grows with log G while every
other line stays near 2 packs. This module replaces phases 2-3 with the
classic HPC recursive-halving/recursive-doubling exchange, keeping the
intra-pod ICI ring (phases 1 and 4) byte-identical:

  phase 2a  recursive-halving reduce-scatter over the pod axis: m =
            floor(log2 G) rounds; in round r pod g pairs with g XOR
            2^(m-1-r), keeps the half of its live range selected by bit
            (m-1-r) of g and sends the other half as a fresh NSD pack.
            After m rounds pod g owns piece [g*L/G2, (g+1)*L/G2) of the
            segment, fully reduced over pods. Non-power-of-two pod counts
            fold pods g >= G2 = 2^m into g - G2 with one extra pack before
            the rounds and receive the finished pack set after them.
  phase 2b  each pod packs its owned piece ONCE; recursive doubling
            forwards the piece packs VERBATIM (no repack), so after m
            rounds every pod holds the identical G2 packs.
  phase 4   the pack set rides around each pod's ICI ring verbatim; every
            node unpacks the SAME packs, so all N results are bit-exact
            equal by construction (the differential tests pin this).

Pack/occupancy accounting vs the tree, per segment:

    sequential packs   (P-1) + ceil(log2 G) + 1    — SAME as the tree
    (an element is re-quantized once per halving round it is sent in, or
    kept and re-quantized at the piece pack; either way depth m+1 inter-
    pod for 2^m pods, and the pre-fold pack supplies the +1 that makes
    ceil(log2 G) for ragged G)

    peak DCN line      every pod sends ~2B(1 - 1/G2) and receives the
    same, vs the tree root's ~2*log2(G)*B each way — the halving the
    ROADMAP asks for at G >= 8, strictly <= the tree from G >= 2.
    ``peak_dcn_bytes`` reports the MEASURED busiest line (sent+received).

Two implementations with identical per-hop math and identical keys (the
sim-vs-shard_map differential in tests/test_butterfly.py is bit-exact):

  * ``butterfly_allreduce_nsd`` — single-process simulation.
  * ``make_butterfly_allreduce`` — shard_map over a (pods, nodes) mesh;
    halving/doubling rounds are ``jax.lax.ppermute`` pairwise exchanges
    of PackedNSD pytrees over the pod axis.

With pods == 1 both collapse to the hierarchy's G == 1 path bit-exactly
(same phase-1 packs, same final-pack key), which pins the degenerate
butterfly == tree differential with zero tolerance.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.quant import wire as wf
from repro.comm.hierarchy import (_INTRA_SALT, _TREE_DOWN_SALT, _hier_shape,
                                  _mesh_axes, tree_rounds)
from repro.comm.reduce_base import (PackCounter, hop_key, pack_hop, seg_len,
                                    segment)
from repro.parallel.axes import auto_axes

_FOLD_SALT = 0xF01D  # non-power-of-two pre-fold packs
_HALVE_SALT = 0xBF1F  # recursive-halving reduce-scatter packs

__all__ = ["ButterflyConfig", "ButterflyTelemetry", "allreduce_butterfly",
           "butterfly_allreduce_nsd", "butterfly_rounds", "dense_reduce_bytes",
           "make_butterfly_allreduce"]


def butterfly_rounds(pods: int) -> int:
    """floor(log2(pods)): halving/doubling rounds over the pod axis."""
    return pods.bit_length() - 1 if pods > 1 else 0


@dataclasses.dataclass(frozen=True)
class ButterflyConfig:
    """Butterfly two-level reduce: N nodes = pods x (N // pods)."""

    pods: int = 2
    s: float = 1.0  # NSD scale for on-wire quantization
    chunk: int = wf.DEFAULT_CHUNK

    def __post_init__(self):
        if self.pods < 1:
            raise ValueError(f"pods must be >= 1, got {self.pods}")


class ButterflyTelemetry(NamedTuple):
    """HierTelemetry's fields; ``peak_dcn_bytes`` is the design target."""

    wire_bytes: jax.Array
    dense_bytes: jax.Array
    error_bound: jax.Array
    n_hops: int
    packs_per_segment: int
    wire_ici_bytes: jax.Array
    wire_dcn_bytes: jax.Array
    pods: int = 1
    per_pod: int = 1
    peak_dcn_bytes: Union[jax.Array, float] = 0.0

    @property
    def ratio(self) -> jax.Array:
        return self.wire_bytes / jnp.maximum(self.dense_bytes, 1.0)


def _zero_telemetry() -> ButterflyTelemetry:
    zero = jnp.float32(0.0)
    return ButterflyTelemetry(zero, zero, zero, 0, 0, zero, zero, 1, 1, zero)


def _piece_len(seg: int, pods: int) -> Tuple[int, int, int]:
    """(m, G2, piece): rounds, power-of-two core, per-pod piece length."""
    m = butterfly_rounds(pods)
    g2 = 1 << m
    return m, g2, -(-seg // g2)


def _hop_counts(g: int, p: int) -> Tuple[int, int]:
    """(ici pack-transfers, dcn pack-transfers) of the whole exchange."""
    m, g2, _ = _piece_len(1, g)
    ici = 2 * g * p * (p - 1)  # phase 1 + phase-4 pack-set forwarding
    # halving sends + doubling sends (one transfer may carry 2^j packs;
    # counted as transfers) + pre/post folds, per segment owner line
    dcn = p * (2 * m * g2 + 2 * (g - g2))
    return ici, dcn


def dense_reduce_bytes(size: int, pods: int, per_pod: int,
                       chunk: int = wf.DEFAULT_CHUNK) -> int:
    """Bytes the same butterfly exchange would move at dense f32.

    ICI matches the hierarchy (same ring phases). DCN: each line moves
    2 * (G - 1) * seg2 elements total (halving + doubling sum to
    seg2*(G2-1) each; folds add 2*seg2 per extra pod), vs the tree's
    2 * (G - 1) * seg — equal up to piece padding.
    """
    seg = seg_len(size, per_pod, chunk)
    _, g2, piece = _piece_len(seg, pods)
    ici = 2 * pods * per_pod * (per_pod - 1) * seg
    dcn = 2 * (pods - 1) * per_pod * piece * g2
    return (ici + dcn) * 4


def butterfly_allreduce_nsd(grads: Union[jax.Array, Sequence[jax.Array]],
                            key: jax.Array,
                            cfg: ButterflyConfig = ButterflyConfig()
                            ) -> Tuple[jax.Array, ButterflyTelemetry]:
    """Simulated butterfly two-level all-reduce of N stacked gradients.

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
    m, G2, _ = _piece_len(1, G)

    flat = grads.astype(jnp.float32).reshape(n, -1)
    acc = [[segment(flat[g * Pn + p], Pn, cfg.chunk)[0] for p in range(Pn)]
           for g in range(G)]
    ctr = PackCounter(Pn)
    traffic = [jnp.float32(0.0) for _ in range(G)]

    def charge(pk, src, dst):
        b = pk.wire_bytes().astype(jnp.float32)
        traffic[src] = traffic[src] + b
        traffic[dst] = traffic[dst] + b

    # --- phase 1: intra-pod ring reduce-scatter (identical to hierarchy:
    # same per-hop math, same keys, so phase-1 packs match bit-exactly) ---
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

    part = [[acc[g][(c - 1) % Pn][c] for c in range(Pn)] for g in range(G)]
    seg = int(part[0][0].shape[0])
    _, _, piece = _piece_len(seg, G)
    seg2 = piece * G2
    if seg2 > seg:
        part = [[jnp.pad(v, (0, seg2 - seg)) for v in row] for row in part]

    # --- phase 2a pre-fold: ragged pods g >= G2 send their whole partial
    # into the power-of-two core with one pack ---
    for g in range(G2, G):
        dst = g - G2
        for c in range(Pn):
            pk = pack_hop(part[g][c], hop_key(key, _FOLD_SALT, 0, g, c),
                          cfg.s, cfg.chunk)
            ctr.count(pk, seg=c, link="dcn")
            charge(pk, g, dst)
            part[dst][c] = part[dst][c] + wf.unpack_nsd(pk)

    # --- phase 2a: recursive-halving reduce-scatter over the pod axis ---
    live = [[part[g][c] for c in range(Pn)] for g in range(G2)]
    for r in range(m):
        bit = m - 1 - r
        half = piece << bit  # live width after this round
        sends = []
        for g in range(G2):
            keep = (g >> bit) & 1
            dst = g ^ (1 << bit)
            for c in range(Pn):
                block = live[g][c][(1 - keep) * half:(2 - keep) * half]
                pk = pack_hop(block, hop_key(key, _HALVE_SALT, r, g, c),
                              cfg.s, cfg.chunk)
                ctr.count(pk, seg=c, link="dcn")
                charge(pk, g, dst)
                sends.append((dst, c, keep, pk))
        nxt = [[None] * Pn for _ in range(G2)]
        for dst, c, keep, pk in sends:
            # the receiver keeps the half the sender sent (they differ in
            # exactly this round's bit, so their live ranges coincide)
            dkeep = 1 - keep
            kept = live[dst][c][dkeep * half:(dkeep + 1) * half]
            nxt[dst][c] = kept + wf.unpack_nsd(pk)
        live = nxt

    # --- phase 2b: pack the owned piece once; recursive doubling forwards
    # the piece packs verbatim until every pod holds the identical set ---
    finals = [[pack_hop(live[g][c],
                        hop_key(key, _TREE_DOWN_SALT, 0, g, c),
                        cfg.s, cfg.chunk)
               for c in range(Pn)] for g in range(G2)]
    for g in range(G2):
        for c in range(Pn):
            ctr.count(finals[g][c], seg=c, link="dcn", hops=0)
    have = [[{g: finals[g][c]} for c in range(Pn)] for g in range(G2)]
    for j in range(m):
        stride = 1 << j
        snap = [[dict(have[g][c]) for c in range(Pn)] for g in range(G2)]
        for g in range(G2):
            dst = g ^ stride
            for c in range(Pn):
                for idx, pk in snap[g][c].items():
                    ctr.count(pk, link="dcn")
                    charge(pk, g, dst)
                    have[dst][c][idx] = pk

    # --- phase 2b post-fold: ragged pods receive the finished pack set ---
    for g in range(G2, G):
        src = g - G2
        for c in range(Pn):
            for pk in have[src][c].values():
                ctr.count(pk, link="dcn")
                charge(pk, src, g)

    # --- phase 4: the pack set rides around each pod's ICI ring verbatim;
    # every node unpacks the SAME G2 packs -> bit-exact consensus ---
    vals = []
    for c in range(Pn):
        for pk in have[0][c].values():
            ctr.count(pk, link="ici", hops=G * (Pn - 1))
        pieces = [wf.unpack_nsd(have[0][c][i]) for i in range(G2)]
        vals.append(jnp.concatenate(pieces)[:seg])

    total = jnp.concatenate(vals)
    size = 1
    for d in shape:
        size *= int(d)
    mean = (total[:size] / n).reshape(shape).astype(dtype)

    ici_hops, dcn_hops = _hop_counts(G, Pn)
    dense = jnp.float32(dense_reduce_bytes(flat.shape[1], G, Pn, cfg.chunk))
    return mean, ButterflyTelemetry(
        wire_bytes=ctr.wire_total, dense_bytes=dense,
        error_bound=jnp.max(ctr.bound) / n, n_hops=ici_hops + dcn_hops,
        packs_per_segment=(Pn - 1) + tree_rounds(G) + 1,
        wire_ici_bytes=ctr.wire["ici"], wire_dcn_bytes=ctr.wire["dcn"],
        pods=G, per_pod=Pn,
        peak_dcn_bytes=(jnp.max(jnp.stack(traffic)) if G > 1
                        else jnp.float32(0.0)))


def _mask_sel(mask: jax.Array, incoming, mine):
    """Per-entry select over the leading (G2) axis of a stacked pack."""
    def sel(a, b):
        mk = mask.reshape((mask.shape[0],) + (1,) * (a.ndim - 1))
        return jnp.where(mk, b, a)
    return jax.tree.map(sel, mine, incoming)


def make_butterfly_allreduce(mesh: Mesh,
                             cfg: ButterflyConfig = ButterflyConfig(),
                             pod_axis: str = "pods",
                             node_axis: str = "nodes"):
    """Build the shard_map butterfly reduce over a 2-D (pods, nodes) mesh.

    Returns ``fn(stacked, key) -> (means, wire_ici, wire_dcn, bounds,
    peak_dcn)`` with ``stacked`` (N, *shape) pod-major over the flattened
    mesh. Per-hop math and keys match ``butterfly_allreduce_nsd``
    bit-exactly; every halving/doubling round is a pairwise
    ``jax.lax.ppermute`` over the pod axis.
    """
    G, Pn = _mesh_axes(mesh, pod_axis, node_axis)
    if cfg.pods != G:
        raise ValueError(f"cfg.pods ({cfg.pods}) != mesh {pod_axis!r} axis "
                         f"size ({G})")
    m, G2, _ = _piece_len(1, G)
    fwd_nodes = [(i, (i + 1) % Pn) for i in range(Pn)]

    def bfly(stacked_local: jax.Array, key: jax.Array):
        local = stacked_local[0]  # (1, *shape) local slice of the stack
        g = jax.lax.axis_index(pod_axis)
        me = jax.lax.axis_index(node_axis)
        shape, dtype = local.shape, local.dtype
        acc, seg = segment(local.astype(jnp.float32).reshape(-1),
                           Pn, cfg.chunk)
        _, _, piece = _piece_len(seg, G)
        seg2 = piece * G2
        ctr = PackCounter(Pn)
        perm_n = partial(jax.lax.ppermute, axis_name=node_axis,
                         perm=fwd_nodes)
        in_core = (g < G2).astype(jnp.float32)
        # this device's share of its pod's DCN line traffic (sent+received)
        dcn_traffic = jnp.float32(0.0)

        # --- phase 1: intra-pod ring reduce-scatter (hierarchy-identical) ---
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

        c_own = (me + 1) % Pn
        live = jnp.pad(jnp.take(acc, c_own, axis=0), (0, seg2 - seg))

        # --- phase 2a pre-fold (SPMD: every device packs; only ragged
        # pods' packs count and cross the wire) ---
        if G2 < G:
            is_extra = (g >= G2).astype(jnp.float32)
            is_rcvr = (g < G - G2).astype(jnp.float32)
            pk = pack_hop(live, hop_key(key, _FOLD_SALT, 0, g, c_own),
                          cfg.s, cfg.chunk)
            ctr.count(pk, seg=c_own, link="dcn", weight=is_extra)
            perm = [(src, src - G2) for src in range(G2, G)]
            pk_in = jax.lax.ppermute(pk, axis_name=pod_axis, perm=perm)
            dcn_traffic += (pk.wire_bytes().astype(jnp.float32) * is_extra
                            + pk_in.wire_bytes().astype(jnp.float32)
                            * is_rcvr)
            # non-receivers get an all-zero pack from ppermute -> add 0
            live = live + wf.unpack_nsd(pk_in)

        # --- phase 2a: recursive halving over the pod axis ---
        for r in range(m):
            bit = m - 1 - r
            half = piece << bit
            keep = (g >> bit) & 1
            block = jax.lax.dynamic_slice(live, ((1 - keep) * half,),
                                          (half,))
            pk = pack_hop(block, hop_key(key, _HALVE_SALT, r, g, c_own),
                          cfg.s, cfg.chunk)
            ctr.count(pk, seg=c_own, link="dcn", weight=in_core)
            perm = [(a, a ^ (1 << bit)) for a in range(G2)]
            pk_in = jax.lax.ppermute(pk, axis_name=pod_axis, perm=perm)
            dcn_traffic += (pk.wire_bytes() + pk_in.wire_bytes()
                            ).astype(jnp.float32) * in_core
            kept = jax.lax.dynamic_slice(live, (keep * half,), (half,))
            live = kept + wf.unpack_nsd(pk_in)

        # --- phase 2b: pack the owned piece once; recursive doubling of
        # the stacked (G2, ...) pack set, entries selected by round mask ---
        pk_mine = pack_hop(live, hop_key(key, _TREE_DOWN_SALT, 0, g,
                                         c_own), cfg.s, cfg.chunk)
        ctr.count(pk_mine, seg=c_own, link="dcn", hops=0, weight=in_core)
        slot = jnp.clip(g, 0, G2 - 1)
        packs = jax.tree.map(
            lambda leaf: jnp.zeros((G2,) + leaf.shape, leaf.dtype
                                   ).at[slot].set(leaf), pk_mine)
        fixed = jnp.float32(wf.HEADER_BYTES
                            + pk_mine.n_chunks * (4 + cfg.chunk // 8))
        ar = jnp.arange(G2)

        def set_bytes(nnz_vec, members):
            """Measured bytes of the pack-set entries ``members`` selects."""
            per = fixed + nnz_vec.astype(jnp.float32)
            return jnp.sum(jnp.where(members, per, 0.0))

        for j in range(m):
            stride = 1 << j
            partner = g ^ stride
            perm = [(a, a ^ stride) for a in range(G2)]
            mine_mask = (ar >> j) == (g >> j)
            in_mask = (ar >> j) == (partner >> j)
            packs_in = jax.lax.ppermute(packs, axis_name=pod_axis, perm=perm)
            b_out = set_bytes(packs.nnz, mine_mask) * in_core
            b_in = set_bytes(packs_in.nnz, in_mask) * in_core
            ctr.count_bytes(b_out, link="dcn")
            dcn_traffic += b_out + b_in
            packs = _mask_sel(in_mask, packs_in, packs)

        # --- phase 2b post-fold: forward the finished set to ragged pods ---
        if G2 < G:
            is_extra = g >= G2
            is_sender = (g < G - G2).astype(jnp.float32)
            perm = [(a, a + G2) for a in range(G - G2)]
            packs_in = jax.lax.ppermute(packs, axis_name=pod_axis, perm=perm)
            every = jnp.ones((G2,), bool)
            b_out = set_bytes(packs.nnz, every) * is_sender
            b_in = (set_bytes(packs_in.nnz, every)
                    * is_extra.astype(jnp.float32))
            ctr.count_bytes(b_out, link="dcn")
            dcn_traffic += b_out + b_in
            packs = jax.tree.map(
                lambda a, b: jnp.where(
                    jnp.reshape(is_extra, (1,) * a.ndim), b, a),
                packs, packs_in)

        # --- phase 4: forward the pack set around the pod ring verbatim ---
        def set_values(pset):
            return jax.vmap(wf.unpack_nsd)(pset).reshape(-1)[:seg]

        out = jnp.zeros_like(acc).at[c_own].set(set_values(packs))
        cur = packs
        every = jnp.ones((G2,), bool)
        for h in range(1, Pn):
            cur = perm_n(cur)
            ctr.count_bytes(set_bytes(cur.nnz, every), link="ici")
            c = (me - h + 1) % Pn
            out = out.at[c].set(set_values(cur))

        # per-segment bound = sum over ALL packs that touched the segment
        bound = jax.lax.psum(ctr.bound, (pod_axis, node_axis))
        size = 1
        for d in shape:
            size *= int(d)
        n = G * Pn
        mean = (out.reshape(-1)[:size] / n).reshape(shape).astype(dtype)
        pod_line = jax.lax.psum(dcn_traffic, node_axis)
        peak = jax.lax.pmax(pod_line, pod_axis)
        return (mean[None], ctr.wire["ici"][None], ctr.wire["dcn"][None],
                (jnp.max(bound) / n)[None], peak[None])

    spec = P((pod_axis, node_axis))
    return jax.jit(jax.shard_map(
        bfly, mesh=auto_axes(mesh),
        in_specs=(spec, P()),
        out_specs=(spec, spec, spec, spec, spec)))


def allreduce_butterfly(grads, key, cfg: ButterflyConfig = ButterflyConfig(),
                        mesh: Mesh = None, pod_axis: str = "pods",
                        node_axis: str = "nodes"
                        ) -> Tuple[jax.Array, ButterflyTelemetry]:
    """Dispatch: shard_map butterfly when a 2-D multi-device mesh is given,
    else the single-process simulation (identical per-hop math)."""
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
        fn = make_butterfly_allreduce(mesh, cfg, pod_axis, node_axis)
        means, w_ici, w_dcn, bounds, peak = fn(grads, key)
        flat_size = 1
        for d in grads.shape[1:]:
            flat_size *= int(d)
        ici_hops, dcn_hops = _hop_counts(G, Pn)
        wire_ici = jnp.sum(w_ici)
        wire_dcn = jnp.sum(w_dcn)
        tele = ButterflyTelemetry(
            wire_bytes=wire_ici + wire_dcn,
            dense_bytes=jnp.float32(
                dense_reduce_bytes(flat_size, G, Pn, cfg.chunk)),
            error_bound=bounds[0], n_hops=ici_hops + dcn_hops,
            packs_per_segment=(Pn - 1) + tree_rounds(G) + 1,
            wire_ici_bytes=wire_ici, wire_dcn_bytes=wire_dcn,
            pods=G, per_pod=Pn, peak_dcn_bytes=peak[0])
        return means[0], tele
    return butterfly_allreduce_nsd(grads, key, cfg)
