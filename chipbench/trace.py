"""Reduction of a JAX profiler trace (``.xplane.pb``) to device times.

Reads the trace with ``jax.profiler.ProfileData`` alone. The device planes
(``/device:TPU:<n>``) carry one event per XLA operation that ran; the host
plane carries the benchmark's own spans (``jax.profiler.TraceAnnotation``),
among them ``window``, which bounds the traced window. All times are in
seconds on the profiler's clock, which the host and device planes share.

- busy: the union of the intervals in which an operation ran on a device,
  within the window, averaged over the devices;
- op time: the time of the operations of one name, with what nests inside
  another operation counted once (self time);
- named time: the union of the intervals of the operations whose name holds
  a given text, such as a kernel's name (a TPU op event carries its HLO
  instruction's text and no named-scope metadata);
- idle gaps: the parts of the window in which a device ran nothing, each
  put down to the host span that covers most of it.
"""
from __future__ import annotations

import dataclasses
from pathlib import Path
from typing import Callable, Iterable

# the benchmark's host spans, innermost first where they nest
SPANS = ("data", "sync", "check", "dispatch")
WINDOW = "window"


@dataclasses.dataclass(frozen=True)
class Op:
    start: float
    end: float
    name: str  # the HLO instruction's name and result shape


def op_name(text: str) -> str:
    """``%fusion.794 = f32[4,8]{1,0:T(8,128)} fusion(...)`` ->
    ``fusion.794 f32[4,8]``: a TPU op event is named by its HLO text."""
    name, _, rest = text.partition(" = ")
    shape = rest.split('{', 1)[0].split(' ', 1)[0].lstrip('(')
    return f"{name.lstrip('%')} {shape}"


def union(intervals: Iterable[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


class Trace:
    def __init__(self, devices: dict[str, list[Op]],
                 spans: list[tuple[float, float, str]]):
        self.devices = {k: sorted(v, key=lambda o: o.start)
                        for k, v in devices.items()}
        self.spans = spans
        windows = [(s, e) for s, e, n in spans if n == WINDOW]
        if not windows:
            raise ValueError("trace holds no 'window' span")
        self.lo, self.hi = windows[0]

    @classmethod
    def load(cls, directory) -> "Trace":
        """The newest ``.xplane.pb`` under ``directory``."""
        from jax.profiler import ProfileData

        files = sorted(Path(directory).rglob("*.xplane.pb"),
                       key=lambda p: p.stat().st_mtime)
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {directory}")
        data = ProfileData.from_file(str(files[-1]))
        devices, spans = {}, []
        names = set(SPANS) | {WINDOW}
        for plane in data.planes:
            if plane.name.startswith("/device:TPU:") and \
                    plane.name[len("/device:TPU:"):].isdigit():
                ops = []
                for line in plane.lines:
                    if line.name != "XLA Ops":
                        continue
                    for ev in line.events:
                        ops.append(Op(ev.start_ns * 1e-9, ev.end_ns * 1e-9,
                                      op_name(ev.name)))
                devices[plane.name] = ops
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name in names:
                            spans.append((ev.start_ns * 1e-9,
                                          ev.end_ns * 1e-9, ev.name))
        if not any(devices.values()):
            raise ValueError(f"no device operations in {files[-1]}")
        return cls(devices, spans)

    def window_s(self) -> float:
        return self.hi - self.lo

    def busy(self, device: str) -> list[tuple[float, float]]:
        return clip(union((o.start, o.end) for o in self.devices[device]),
                    self.lo, self.hi)

    def busy_s(self) -> float:
        return sum(length(self.busy(d)) for d in self.devices) / len(
            self.devices)

    def matching_s(self, pred: Callable[[Op], bool]) -> float:
        """Union time of the matching operations, averaged over devices."""
        return sum(length(clip(union((o.start, o.end) for o in ops
                                     if pred(o)), self.lo, self.hi))
                   for ops in self.devices.values()) / len(self.devices)

    def self_times(self, ops: list[Op]) -> dict[str, float]:
        """Per op name, its time less that of the operations nested in it."""
        out: dict[str, float] = {}
        stack: list[list] = []  # [end, name, self time]

        def close(upto: float) -> None:
            while stack and stack[-1][0] <= upto:
                end, name, t = stack.pop()
                out[name] = out.get(name, 0.0) + t

        for o in ops:
            close(o.start)
            s, e = max(o.start, self.lo), min(o.end, self.hi)
            t = max(e - s, 0.0)
            if stack:
                stack[-1][2] -= t
            stack.append([o.end, o.name, t])
        close(float("inf"))
        return out

    def top_ops(self, n: int) -> list[list]:
        """The ``n`` operation names with the most self time, averaged over
        devices, in seconds."""
        total: dict[str, float] = {}
        for ops in self.devices.values():
            for k, v in self.self_times(ops).items():
                total[k] = total.get(k, 0.0) + v / len(self.devices)
        return [[k, v] for k, v in sorted(total.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def gaps(self, device: str) -> list[tuple[float, float]]:
        busy, out, t = self.busy(device), [], self.lo
        for s, e in busy:
            if s > t:
                out.append((t, s))
            t = max(t, e)
        if t < self.hi:
            out.append((t, self.hi))
        return out

    def host_span_at(self, s: float, e: float) -> str:
        """The host span that covers most of [s, e]; of spans that cover
        as much, the innermost."""
        best, cover = "none", 0.0
        for name in SPANS:
            c = length(clip(union((a, b) for a, b, n in self.spans
                                  if n == name), s, e))
            if c > cover:
                best, cover = name, c
        return best

    def idle_gaps(self, n: int) -> list[list]:
        """The ``n`` longest idle gaps of any device, with the host span
        that covers most of each, in seconds."""
        gaps = sorted(((e - s, s, e) for d in self.devices
                       for s, e in self.gaps(d)), reverse=True)[:n]
        return [[self.host_span_at(s, e), d] for d, s, e in gaps]
