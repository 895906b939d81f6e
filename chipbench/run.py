"""Chip benchmark harness: one run of one cell.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is found by name: the cell in ``BENCHMARK.json``,
its workload in ``workloads/<cell>.json``, its configuration in
``configs/<config>.json``, its path through the program in
``drivers/<driver>.py``, its model family in ``models/<family>.py`` and
``reference/<family>.py``, and each metric, end-to-end or per-layer, in
``metrics/<metric>.py``. A new cell, configuration, path or metric adds
files: the driver returns its run's readings and the numbers that decide
``correct``, and each metric's reader takes its value from them.

With ``--trace 0`` the run reports the cell's end-to-end metrics; with
``--trace 1`` it profiles the same window and reports its per-layer metrics
instead. The last line of standard output is one JSON object; the last lines
of standard error are the numbers that decided ``correct``, each with its
limit. A run that finds no TPU, or fewer chips than the cell asks for, exits
non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_json(path: Path):
    with open(path) as fh:
        return json.load(fh)


class Cell:
    """One cell as a run sees it: its files, its seed and its window."""

    def __init__(self, name: str, seed: int, seconds: float, trace: bool,
                 spec: dict, config: dict, workload: dict):
        self.name, self.seed, self.seconds, self.trace = (name, seed,
                                                          seconds, trace)
        self.config, self.workload = config, workload
        self.chips = spec["chips"]
        self.t_start = T_START

    @classmethod
    def from_files(cls, name: str, seed: int, seconds: float,
                   trace: bool) -> "Cell":
        bench = load_json(ROOT / "BENCHMARK.json")
        spec = next((w for w in bench["workloads"] if w["name"] == name),
                    None)
        if spec is None:
            raise SystemExit(f"chipbench: no cell {name!r} in BENCHMARK.json")
        config = load_json(BENCH / "configs" / f"{spec['config']}.json")
        workload = load_json(BENCH / "workloads" / f"{name}.json")
        return cls(name, seed, seconds, trace, spec, config, workload)

    def family(self):
        return importlib.import_module(
            f"chipbench.models.{self.config['family']}")

    def reference(self):
        return importlib.import_module(
            f"chipbench.reference.{self.config['family']}")

    def flops(self):
        return importlib.import_module(
            f"chipbench.flops.{self.config['family']}")

    @staticmethod
    def span(name: str):
        """A host span on the profiler's clock (``trace.SPANS``, ``window``)."""
        import jax

        return jax.profiler.TraceAnnotation(name)


def enable_compile_cache() -> str:
    """JAX's persistent cache at a fixed path inside the checkout.

    ``JAX_COMPILATION_CACHE_DIR``, where set, is read by JAX itself. Every
    program is cached, however quickly it compiled, so that a second run
    compiles nothing.
    """
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def require_chips(n: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chipbench: JAX found no TPU (platform "
                         f"{devices[0].platform!r}); nothing was run")
    if len(devices) < n:
        raise SystemExit(f"chipbench: the cell needs {n} chips, JAX found "
                         f"{len(devices)}")
    return devices


class CompileCounter:
    """Counts traces and compiles (or cache loads) while ``active``."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.active, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if self.active and event in self.EVENTS:
            self.count += 1


def read_metrics(cell: Cell, out: dict, trace) -> dict:
    """The cell's end-to-end metrics (no trace) or its per-layer metrics
    (traced), each read by its own reader; a reader that finds nothing to
    read returns None and its metric is left out. ``main`` has refused a
    device kind that has no peaks."""
    bench = load_json(ROOT / "BENCHMARK.json")
    ctx = {"cell": cell, "trace": trace, "out": out,
           "peaks": load_json(BENCH / "peaks.json").get(out["device_kind"])}
    metrics = {}
    for m in bench["end_to_end" if trace is None else "per_layer"]:
        if cell.name not in m.get("workloads", [cell.name]):
            continue
        reader = importlib.import_module(f"chipbench.metrics.{m['name']}")
        value = reader.read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics


def result(cell: Cell, out: dict) -> dict:
    """The run's result line, from what the driver returned."""
    from chipbench import check

    correct, checks = check.judge(out["numbers"], cell.workload["limits"])
    device = {"platform": out["devices"][0].platform,
              "kind": out["device_kind"], "count": out["device_count"],
              "memory_peak_bytes": out["memory_peak_bytes"]}
    trace = None
    if cell.trace:
        from chipbench.trace import Trace

        trace = Trace.load(out["trace_dir"])
        device.update(busy_s=trace.busy_s(), window_s=trace.window_s())
    line = {"correct": correct, "attempted": out["attempted"],
            "failed": 0 if correct else out["attempted"],
            "metrics": read_metrics(cell, out, trace), "device": device}
    if trace is not None:
        line["breakdown"] = {"device_ops": trace.top_ops(10),
                             "idle_gaps": trace.idle_gaps(10)}
    line["checks"] = checks
    return line


def run_cell(cell: Cell, devices: list, kind: str) -> dict:
    """Set up, measure and check one run of ``cell``; its result line."""
    trace_dir = tempfile.mkdtemp(prefix="chipbench-") if cell.trace else None
    try:
        driver = importlib.import_module(
            f"chipbench.drivers.{cell.workload['driver']}")
        out = driver.run(cell, devices[:cell.chips], CompileCounter(),
                         trace_dir)
        out.update(devices=devices[:cell.chips], device_kind=kind,
                   device_count=len(devices), trace_dir=trace_dir)
        print(f"chipbench: {out['attempted']} attempted in "
              f"{out['window_s']!r} s, set-up {out['setup_s']!r} s, "
              f"compiles in the window {out['window_compiles']}",
              file=sys.stderr)
        return result(cell, out)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = Cell.from_files(args.workload, args.seed, args.seconds,
                           bool(args.trace))
    cache = enable_compile_cache()
    devices = require_chips(cell.chips)
    kind = devices[0].device_kind
    print(f"chipbench: platform {devices[0].platform}, device_kind {kind!r}, "
          f"{len(devices)} device(s); compile cache {cache}", file=sys.stderr)
    if kind not in load_json(BENCH / "peaks.json"):
        raise SystemExit(f"chipbench: no peaks for device_kind {kind!r} in "
                         f"chipbench/peaks.json")
    line = run_cell(cell, devices, kind)
    for k, c in line["checks"].items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    # run as a script: the checkout's root, not this directory, leads the
    # import path (a module here must not shadow the standard library)
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))
    sys.exit(main())
