"""Shared machinery for compressed reduces (flat ring + hierarchical).

Both topologies in ``repro.comm`` move packed NSD segments between nodes
and account for the same three things the same way:

  * segmenting      a flat gradient is padded and split into chunk-aligned
                    segments, one per ring position;
  * hop keys        every pack that crosses a link gets a fresh PRNG key
                    folded from (salt, *position indices) so re-dither
                    noise is i.i.d. across hops, nodes, and levels;
  * accounting      wire bytes are MEASURED per pack (never estimated) and
                    the pointwise error bound is the running sum of the
                    Deltas of every pack whose quantization error lands in
                    a segment's final value (paper eq. 5/6 + |Q(x)-x| <=
                    Delta pointwise).

``ring.py`` and ``hierarchy.py`` import these helpers instead of each
carrying a private copy; the simulation and shard_map paths of both reduce
implementations share them too, which is what makes the sim-vs-shard_map
differential tests bit-exact.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.core import nsd
from repro.quant import wire as wf


class ReduceTelemetry(NamedTuple):
    """Per-reduce accounting shared by the flat ring and the hierarchy.

    ``packs_per_segment`` is the SEQUENTIAL pack depth: how many times one
    segment's value is re-quantized on its way to the final mean (the flat
    ring's N vs the hierarchy's (P-1) + ceil(log2 G) + 1). The error bound
    additionally sums the Deltas of packs from *other* nodes that merge
    into the segment, so it is not simply proportional to this count.
    """

    wire_bytes: jax.Array  # f32 scalar: total bytes crossing all links
    dense_bytes: jax.Array  # f32 scalar: same exchange at dense f32
    error_bound: jax.Array  # f32 scalar: max pointwise |result - mean| bound
    n_hops: int  # static: total link traversals
    packs_per_segment: int = 0  # static: sequential re-quantizations

    @property
    def ratio(self) -> jax.Array:
        return self.wire_bytes / jnp.maximum(self.dense_bytes, 1.0)


def seg_len(size: int, n: int, chunk: int) -> int:
    """Segment length: ceil(size / n) rounded up to a chunk multiple."""
    seg = -(-size // n)
    return -(-seg // chunk) * chunk


def segment(flat: jax.Array, n: int, chunk: int) -> Tuple[jax.Array, int]:
    """Pad a flat vector so it splits into n chunk-aligned segments."""
    size = flat.shape[0]
    seg = seg_len(size, n, chunk)
    padded = jnp.pad(flat, (0, n * seg - size))
    return padded.reshape(n, seg), seg


def pack_hop(x: jax.Array, key: jax.Array, s: float, chunk: int
             ) -> wf.PackedNSD:
    """NSD-pack ``x`` for one hop, with a Delta at which no level clips.

    Delta is ``wf.pack_nsd``'s s * std, raised to max|x| / INT8_CLIP where
    that is larger. A heavy-tailed segment, such as an embedding gradient
    whose few touched rows hold all its mass, would otherwise clip its
    largest entries at +-127 Delta: the error there is unbounded by Delta
    and biased, and the error bound the reduce sums would not hold.
    """
    delta = jnp.maximum(nsd.compute_delta(x, s),
                        jnp.max(jnp.abs(x.astype(jnp.float32)))
                        / nsd.INT8_CLIP)
    k = nsd.nsd_indices(x, key, delta)
    return wf.pack_indices(k, delta, x.shape, x.dtype, chunk)


def hop_key(key: jax.Array, salt: int, *indices) -> jax.Array:
    """Fresh per-pack key: fold (salt, i0, i1, ...) into the base key.

    Indices may be Python ints or traced scalars (``jax.lax.axis_index``
    inside shard_map), so the sim and shard_map paths derive identical
    keys for the same logical pack.
    """
    k = jax.random.fold_in(key, salt)
    for i in indices:
        k = jax.random.fold_in(k, i)
    return k


class PackCounter:
    """Running wire-byte (per link class) + per-segment Delta accounting.

    ``weight`` lets the SPMD shard_map paths count a pack only on the
    device that actually sends it (a traced 0/1 mask); the sim paths call
    with the default weight of 1.
    """

    def __init__(self, n_segments: int):
        self.wire = {"ici": jnp.float32(0.0), "dcn": jnp.float32(0.0)}
        self.bound = jnp.zeros((n_segments,), jnp.float32)

    def count(self, packed, seg=None, link: str = "ici", hops: int = 1,
              weight=None) -> None:
        """Record a pack crossing ``hops`` links of class ``link``.

        ``seg`` (static or traced index) additionally charges the pack's
        Delta to that segment's error bound; pass None for forwarded-
        verbatim hops, whose error was already charged at pack time.
        """
        b = packed.wire_bytes().astype(jnp.float32) * hops
        d = packed.deltas[0]
        if weight is not None:
            w = weight.astype(jnp.float32) if hasattr(weight, "astype") \
                else jnp.float32(weight)
            b = b * w
            d = d * w
        self.wire[link] = self.wire[link] + b
        if seg is not None:
            self.bound = self.bound.at[seg].add(d)

    def count_bytes(self, nbytes, link: str = "ici") -> None:
        """Record raw bytes crossing a link class (already-packed payloads
        forwarded verbatim, e.g. a stacked set of piece packs — no Delta
        charge, the error was charged when each pack was created)."""
        self.wire[link] = self.wire[link] + jnp.float32(0.0) + nbytes

    @property
    def wire_total(self) -> jax.Array:
        return self.wire["ici"] + self.wire["dcn"]
