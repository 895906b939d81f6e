"""Device time by named scope, on traces recorded on one TPU v5e chip
(``chipbench/testdata/record.py``, ``chipbench/traces/record_steps.py``)."""
import json
from pathlib import Path

import pytest

from chipbench import scopes
from chipbench.tests.conftest import ROOT
from chipbench.trace import Trace

BENCH = Path(__file__).resolve().parents[1]
SMALL = BENCH / "testdata" / "small.xplane.pb"
STEPS = BENCH / "traces" / "steps.xplane.pb"


def load(path, recorder):
    if not path.exists():
        pytest.fail(f"{path} is missing: record it on a chip with "
                    f"chipbench/{recorder}")
    return Trace.load(path.parent)


def test_components_unwrap_transforms():
    assert scopes.components(
        "jit(_step)/step/grad/transpose(jvp(head))/mul:") == (
        "_step", "step", "grad", "head", "mul")
    parts = scopes.components("a/transpose(step/grad)/jvp(head)/dither/bwd/"
                              "nsd/x;b/c")
    assert parts == ("a", "step", "grad", "head", "dither", "bwd", "nsd", "x")
    assert scopes.holds(parts, "dither/bwd")
    assert scopes.holds(parts, "head")
    assert not scopes.holds(parts, "bwd/dither")
    assert not scopes.holds(scopes.components("jvp(header)/x"), "head")


def test_recorded_kernel_maps_to_its_pallas_call():
    trace = load(SMALL, "testdata/record.py")
    ops = scopes.device_ops(str(SMALL.parent))
    assert list(ops) == ["/device:TPU:0"]
    nsd = {parts for _, _, parts, name in ops["/device:TPU:0"]
           if name.startswith("nsd_quantize_blocked")}
    assert nsd == {("step", "nsd_quantize_blocked", "pallas_call")}
    by_scope = scopes.scope_s(trace, SMALL.parent, "nsd_quantize_blocked")
    by_name = trace.matching_s(lambda op: "nsd_quantize_blocked" in op.name)
    assert by_scope > 0
    assert by_scope == pytest.approx(by_name, rel=1e-12)


@pytest.fixture(scope="module")
def steps():
    return load(STEPS, "traces/record_steps.py")


def read_scopes():
    """The scope every scope metric's reader reads, by metric name."""
    out = {}
    for m in json.loads((ROOT / "BENCHMARK.json").read_text())["per_layer"]:
        text = (ROOT / "chipbench" / "metrics" / f"{m['name']}.py").read_text()
        if "scopes.per_step_ms" in text:
            out[m["name"]] = text.split('per_step_ms(ctx, "')[1].split('"')[0]
    return out


def test_every_metric_reads_a_scope():
    assert read_scopes() == {"dither_bwd_ms": "dither/bwd",
                             "ssd_ms": "mixer/ssd", "head_ms": "head",
                             "optimizer_ms": "step/update"}


@pytest.mark.parametrize("scope", sorted(set(read_scopes().values())))
def test_recorded_steps_hold_each_read_scope(steps, scope):
    t = scopes.scope_s(steps, STEPS.parent, scope)
    assert 0 < t < steps.busy_s()


def test_recorded_steps_breakdown(steps):
    b = scopes.breakdown(steps, STEPS.parent,
                         ["dither/bwd", "layers", "head", "embed",
                          "step/update"])
    assert 0 < b["covered_pct"] <= 100.0 + 1e-9
    assert all(0 < v <= 100.0 + 1e-9 for v in b["scopes_pct"].values())
    assert b["unscoped_s"]
