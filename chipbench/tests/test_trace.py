"""The trace reduction, on synthetic intervals and on a trace recorded on
one TPU v5e chip (``chipbench/testdata/record.py``)."""
from pathlib import Path

import pytest

from chipbench.trace import Op, Trace, op_name, union

SMALL = Path(__file__).resolve().parents[1] / "testdata" / "small.xplane.pb"


def synthetic():
    ops = [Op(0.0, 4.0, "while.1 f32[8]"), Op(1.0, 2.0, "fusion.2 f32[8]"),
           Op(2.5, 3.0, "kernel.3 f32[8]"), Op(6.0, 7.0, "fusion.2 f32[8]")]
    spans = [(0.0, 10.0, "window"), (0.0, 9.0, "dispatch"),
             (4.0, 5.5, "data"), (9.0, 10.0, "sync")]
    return Trace({"/device:TPU:0": ops}, spans)


def test_union_merges_overlaps():
    assert union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [(0, 2.5), (3, 4)]


def test_busy_gaps_and_spans():
    t = synthetic()
    assert t.window_s() == 10.0
    assert t.busy_s() == 5.0
    assert t.gaps("/device:TPU:0") == [(4.0, 6.0), (7.0, 10.0)]
    # the 3 s gap lies in dispatch (2 s) and sync (1 s); the 2 s gap in
    # data (1.5 s, innermost) and dispatch (2 s)
    assert t.idle_gaps(5) == [["dispatch", 3.0], ["dispatch", 2.0]]
    assert t.host_span_at(4.0, 5.5) == "data"


def test_self_time_counts_nested_ops_once():
    tops = dict(synthetic().top_ops(10))
    assert tops == {"while.1 f32[8]": 2.5, "fusion.2 f32[8]": 2.0,
                    "kernel.3 f32[8]": 0.5}
    assert sum(tops.values()) == synthetic().busy_s()


def test_op_name_from_hlo_text():
    assert op_name("%fusion.794 = f32[4,8]{1,0:T(8,128)} fusion(bf16[2] %a)"
                   ) == "fusion.794 f32[4,8]"
    assert op_name("%nsd_quantize_blocked.25 = (s8[8,128]{1,0}, s32[1]{0}) "
                   "custom-call(%p)") == "nsd_quantize_blocked.25 s8[8,128]"


@pytest.fixture(scope="module")
def small():
    if not SMALL.exists():
        pytest.fail(f"{SMALL} is missing: record it with "
                    f"chipbench/testdata/record.py on a chip")
    return Trace.load(SMALL.parent)


def test_recorded_trace(small):
    assert list(small.devices) == ["/device:TPU:0"]
    assert 0 < small.busy_s() < small.window_s()
    # the three longest gaps are the 20 ms sleeps in 'data'
    gaps = small.idle_gaps(3)
    assert [name for name, _ in gaps] == ["data"] * 3
    assert all(0.019 < s < 0.03 for _, s in gaps)
    kernel = small.matching_s(lambda op: "nsd_quantize_blocked" in op.name)
    assert 0 < kernel < small.busy_s()
    names = [n for n, _ in small.top_ops(50)]
    assert any(n.startswith("nsd_quantize_blocked") for n in names)
