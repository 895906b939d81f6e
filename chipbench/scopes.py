"""Device time by named scope, from the op metadata of a profiler trace.

Each device op's event metadata in an ``.xplane.pb`` carries a ``tf_op``
stat: the JAX name stack of the HLO instruction, such as
``jit(_step)/step/grad/transpose(jvp())/while/body/closed_call/checkpoint/
mixer/in_proj/dither/bwd/nsd/jit(nsd_quantize_blocked)/pallas_call``. The
program names its parts with ``jax.named_scope`` (``repro.obs.trace``), so
the time of a scope is the union of the intervals of the ops whose name
stack holds it, within the ``window`` span. ``jax.profiler.ProfileData``
does not expose the metadata's stats, so the trace is read with the
``xplane_pb2`` module that ships in the installed tensorflow, loaded by its
path (it needs only ``google.protobuf``; tensorflow itself is not
imported). Where that file is missing, every scope reads nothing.

A name stack is compared without its transform wrappers: ``jit(_step)``,
``jvp(head)`` and ``transpose(jvp(head))`` read ``_step``, ``head`` and
``head``. A scope path such as ``mixer/ssd`` matches whole components in
a row. An op with several ``;``-joined names counts under its first.
"""
from __future__ import annotations

import functools
import importlib.util
import re
from pathlib import Path

from chipbench.trace import Op, clip, length, op_name, union

XPLANE_PB2 = ("tsl", "profiler", "protobuf", "xplane_pb2.py")
DEVICE = "/device:TPU:"
_WRAPPER = re.compile(r"[\w.\-]+\(|\)")


@functools.lru_cache(maxsize=None)
def xplane_pb2():
    """The shipped ``xplane_pb2`` module, or None where it is missing."""
    spec = importlib.util.find_spec("tensorflow")
    if spec is None or not spec.submodule_search_locations:
        return None
    path = Path(spec.submodule_search_locations[0]).joinpath(*XPLANE_PB2)
    if not path.exists():
        return None
    mod_spec = importlib.util.spec_from_file_location(
        "chipbench_xplane_pb2", path)
    module = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(module)
    return module


def components(tf_op: str) -> tuple[str, ...]:
    """The scope components of a ``tf_op`` stat (``<name stack>:<type>``,
    its first name where there are several)."""
    first = tf_op.split(";", 1)[0].rsplit(":", 1)[0]
    return tuple(p for p in _WRAPPER.sub("", first).split("/") if p)


def holds(parts: tuple[str, ...], scope: str) -> bool:
    want = tuple(scope.split("/"))
    n = len(want)
    return any(parts[i:i + n] == want for i in range(len(parts) - n + 1))


@functools.lru_cache(maxsize=4)
def device_ops(directory: str) -> dict[str, list[tuple[float, float,
                                                        tuple[str, ...],
                                                        str]]]:
    """Per device plane, each op as ``(start, end, scope components, op
    name)`` in seconds on the profiler's clock, from the newest
    ``.xplane.pb`` under ``directory``; empty where no reader is
    installed. Times are whole nanoseconds, as ``ProfileData`` gives them
    (``chipbench.trace``)."""
    pb2 = xplane_pb2()
    files = sorted(Path(directory).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if pb2 is None or not files:
        return {}
    space = pb2.XSpace()
    space.ParseFromString(files[-1].read_bytes())
    out = {}
    for plane in space.planes:
        if not (plane.name.startswith(DEVICE)
                and plane.name[len(DEVICE):].isdigit()):
            continue
        tf_op = next((k for k, v in plane.stat_metadata.items()
                      if v.name == "tf_op"), None)
        stacks = {}
        for mid, md in plane.event_metadata.items():
            stat = next((s for s in md.stats if s.metadata_id == tf_op),
                        None)
            stack = ("" if stat is None else
                     plane.stat_metadata[stat.ref_value].name
                     if stat.WhichOneof("value") == "ref_value"
                     else stat.str_value)
            stacks[mid] = (components(stack), op_name(md.name))
        ops = []
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                start = line.timestamp_ns + ev.offset_ps // 1000
                end = start + ev.duration_ps // 1000
                ops.append((start * 1e-9, end * 1e-9)
                           + stacks[ev.metadata_id])
        out[plane.name] = ops
    return out


def scope_s(trace, directory: str, scope: str) -> float:
    """Union time of the ops under ``scope`` within ``trace``'s window,
    averaged over the devices; 0 where nothing matches."""
    ops = device_ops(str(directory))
    if not ops:
        return 0.0
    return sum(length(clip(union((s, e) for s, e, parts, _ in dev
                                 if holds(parts, scope)),
                           trace.lo, trace.hi))
               for dev in ops.values()) / len(ops)


def per_step_ms(ctx, scope: str):
    """A scope's device time per step of the traced window, in ms; None
    where the trace holds no op under it."""
    t = scope_s(ctx["trace"], ctx["out"]["trace_dir"], scope)
    if t == 0.0:
        return None
    return 1e3 * t / ctx["out"]["steps"]


def breakdown(trace, directory: str, scopes, n: int = 10) -> dict:
    """Each scope's share of the window's busy time, the share of the
    scopes together, and the ``n`` ops under none of them with the most
    self time (``Trace.self_times``), averaged over devices, in seconds."""
    ops = device_ops(str(directory))
    busy = trace.busy_s()
    shares = {s: 100.0 * scope_s(trace, directory, s) / busy for s in scopes}
    covered, unscoped = 0.0, {}
    for dev in ops.values():
        inside = [(s, e) for s, e, parts, _ in dev
                  if any(holds(parts, sc) for sc in scopes)]
        covered += length(clip(union(inside), trace.lo, trace.hi))
        outside = {name for _, _, parts, name in dev
                   if not any(holds(parts, sc) for sc in scopes)}
        self_s = trace.self_times(sorted(
            (Op(s, e, name) for s, e, _, name in dev),
            key=lambda o: o.start))
        for name in outside:
            unscoped[name] = unscoped.get(name, 0.0) + self_s.get(
                name, 0.0) / len(ops)
    top = sorted(unscoped.items(), key=lambda kv: -kv[1])[:n]
    return {"scopes_pct": shares,
            "covered_pct": 100.0 * covered / max(len(ops), 1) / busy,
            "unscoped_s": [[k, v] for k, v in top]}
