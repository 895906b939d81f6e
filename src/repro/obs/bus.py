"""The metrics bus: one typed emission path for all in-jit telemetry.

Replaces the three copy-pasted sinks of ``repro.core.stats``
(``_SINK``/``_COMM_SINK``/``_MEM_SINK``) with a single registry-backed
store. Emission from inside jitted code — ``custom_vjp`` backward passes,
shard_map bodies — goes through one ``jax.experimental.io_callback`` path
(:func:`MetricsBus.emit`); host-side producers (the span tracer, the
trainer's per-step metrics) append directly via :func:`MetricsBus.record`.

Readers (``rows`` / ``rows_since`` / ``row_count`` / ``summary`` helpers in
``repro.core.stats``) first *drain*: ``jax.effects_barrier()`` blocks until
every dispatched-but-unfinished step's callbacks have landed, so a reader
never races an in-flight emission (the seed repo's flaky-telemetry fix,
now centralized here).

Stacked views are cached per (stream, tag) *generation*: ``rows()`` on an
unchanged tag returns the cached ``np.stack`` instead of restacking the
full history — end-of-run summaries on long runs used to be O(n^2) in the
row count (every ``summary()`` call restacked everything). The cache is
pinned by a call-count test on the stack path (tests/test_obs.py).

Monitor events are host-side structured dicts, not float rows; they live
in a parallel event log on the same bus so the run-log exporter drains
both through one cursor protocol.
"""
from __future__ import annotations

import functools
import threading
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from repro.obs.streams import MetricStream, StreamRegistry


class MetricsBus:
    """Thread-safe process-local store of typed telemetry rows."""

    def __init__(self):
        self.registry = StreamRegistry()
        self._lock = threading.Lock()
        # (stream, tag) -> list of (ncols,) float32 rows
        self._rows: Dict[Tuple[str, str], List[np.ndarray]] = {}
        # (stream, tag) -> (generation == len at stack time, stacked view)
        self._stacked: Dict[Tuple[str, str], Tuple[int, np.ndarray]] = {}
        # structured (non-numeric) event records, in arrival order
        self._events: List[Dict[str, Any]] = []
        # instrumentation for the O(n^2)-restack regression pin
        self.stack_calls = 0

    # ------------------------------------------------------------ lifecycle
    def reset(self) -> None:
        with self._lock:
            self._rows.clear()
            self._stacked.clear()
            self._events.clear()

    @staticmethod
    def drain() -> None:
        """Block until in-flight io_callbacks have landed (readers call
        this: emissions from a dispatched-but-undrained step would
        otherwise race the read)."""
        import jax

        jax.effects_barrier()

    # ------------------------------------------------------------- writers
    def record(self, stream: str, tag: str, row) -> None:
        """Host-side append of one row (also the io_callback landing pad).

        A device array (``jax.Array``) is kept as it is and reaches the
        host only when a reader stacks it, so a producer can record a
        step's on-device values without waiting for the step."""
        import jax

        spec = self.registry.get(stream)
        if isinstance(row, jax.Array):
            arr = row.astype(np.float32).reshape(-1)
        else:
            arr = np.asarray(row, np.float32).reshape(-1)
        if arr.shape != (spec.ncols,):
            raise ValueError(
                f"stream {stream!r} expects {spec.ncols} columns "
                f"{spec.columns}, got row of shape {arr.shape}")
        with self._lock:
            self._rows.setdefault((stream, tag), []).append(arr)

    def emit(self, stream: str, tag: str, values) -> None:
        """Record one row from inside a (possibly jitted) computation.

        ``values`` is a traced float vector matching the stream's declared
        arity; the row lands on whatever bus is current when the callback
        executes (so a test swapping the default bus mid-flight keeps the
        legacy sink semantics).
        """
        import jax
        import jax.numpy as jnp

        self.registry.get(stream)  # fail at trace time on unknown streams
        jax.experimental.io_callback(
            functools.partial(_landing_pad, stream, tag),
            jax.ShapeDtypeStruct((), jnp.int32),
            jnp.asarray(values, jnp.float32),
            ordered=False,
        )

    def log_event(self, event: Dict[str, Any]) -> None:
        """Append one structured (dict) event — monitor trips etc."""
        with self._lock:
            self._events.append(dict(event))

    # ------------------------------------------------------------- readers
    def _empty(self, stream: str) -> np.ndarray:
        return np.zeros((0, self.registry.get(stream).ncols), np.float32)

    def rows(self, stream: str, tag: str) -> np.ndarray:
        """(n, ncols) array of every recorded row for a (stream, tag).

        The stacked view is cached per generation: repeated reads of an
        unchanged tag cost O(1), not O(n) — and end-of-run summaries that
        loop tags x metrics stop being O(n^2) overall.
        """
        self.drain()
        key = (stream, tag)
        with self._lock:
            rows = self._rows.get(key)
            if not rows:
                return self._empty(stream)
            gen = len(rows)
            cached = self._stacked.get(key)
            if cached is not None and cached[0] == gen:
                return cached[1]
            stacked = np.stack(rows)
            self.stack_calls += 1
            self._stacked[key] = (gen, stacked)
            return stacked

    def rows_since(self, stream: str, tag: str, start: int) -> np.ndarray:
        """Rows from index ``start`` on, stacking only the new suffix —
        per-step consumers (controller telemetry windows, the run-log
        exporter) stay O(new records) per tick."""
        self.drain()
        with self._lock:
            new = self._rows.get((stream, tag), [])[start:]
            if not new:
                return self._empty(stream)
            self.stack_calls += 1
            return np.stack(new)

    def row_count(self, stream: str, tag: str) -> int:
        self.drain()
        with self._lock:
            return len(self._rows.get((stream, tag), []))

    def tags(self, stream: str) -> List[str]:
        self.drain()
        with self._lock:
            return sorted(t for (s, t), r in self._rows.items()
                          if s == stream and r)

    def streams_present(self) -> List[str]:
        """Stream names that hold at least one row."""
        self.drain()
        with self._lock:
            return sorted({s for (s, _t), r in self._rows.items() if r})

    def events(self, start: int = 0) -> List[Dict[str, Any]]:
        with self._lock:
            return [dict(e) for e in self._events[start:]]

    def event_count(self) -> int:
        with self._lock:
            return len(self._events)

    def cursors(self) -> Dict[Tuple[str, str], int]:
        """Snapshot of current row counts, for incremental exporters."""
        self.drain()
        with self._lock:
            return {k: len(v) for k, v in self._rows.items() if v}


def _landing_pad(stream: str, tag: str, row) -> np.ndarray:
    """io_callback target: route to whatever bus is default *now*."""
    get_bus().record(stream, tag, np.asarray(row))
    return np.zeros((), np.int32)


# ---------------------------------------------------------------------------
# the process default bus (what core/stats and the tracer write to)
# ---------------------------------------------------------------------------

_DEFAULT: Optional[MetricsBus] = None
_DEFAULT_LOCK = threading.Lock()


def get_bus() -> MetricsBus:
    global _DEFAULT
    if _DEFAULT is None:
        with _DEFAULT_LOCK:
            if _DEFAULT is None:
                _DEFAULT = MetricsBus()
    return _DEFAULT


def set_bus(bus: Optional[MetricsBus]) -> MetricsBus:
    """Swap the process default (tests); returns the new default."""
    global _DEFAULT
    with _DEFAULT_LOCK:
        _DEFAULT = bus
    return get_bus()


def register_stream(stream: MetricStream) -> MetricStream:
    return get_bus().registry.register(stream)
