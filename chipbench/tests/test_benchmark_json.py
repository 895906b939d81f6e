"""Every name in BENCHMARK.json resolves to a file of its own under
chipbench/, as the harness looks it up."""
import importlib
import json
import re

from chipbench.tests.conftest import ROOT

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")


def test_names_and_files():
    cells = {w["name"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert NAME.fullmatch(c["name"])
        config = json.loads((ROOT / c["file"]).read_text())
        assert config["reduced"] == c["reduced"]
        importlib.import_module(f"chipbench.models.{config['family']}")
        importlib.import_module(f"chipbench.reference.{config['family']}")
        importlib.import_module(f"chipbench.flops.{config['family']}")
    for w in BENCH["workloads"]:
        assert NAME.fullmatch(w["name"]) and len(w["why"]) <= 200
        workload = json.loads((ROOT / "chipbench" / "workloads" /
                               f"{w['name']}.json").read_text())
        assert workload["config"] == w["config"]
        importlib.import_module(f"chipbench.drivers.{workload['driver']}")
    for m in BENCH["per_layer"]:
        assert set(m["workloads"]) <= cells
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert callable(importlib.import_module(
            f"chipbench.metrics.{m['name']}").read)
