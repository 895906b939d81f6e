"""Small sizes of the benchmark's cells, for the CPU."""
from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

# mamba2 at the smallest widths the kernels' 128-tiles and the SSD chunks
# still exercise: 2 layers, 2 heads, 2 chunks per sequence
SMALL = {"family": "mamba2", "d_model": 64, "n_layer": 2, "vocab_size": 500,
         "pad_vocab_size_multiple": 16, "norm_eps": 1e-6,
         "ssm_cfg": {"layer": "Mamba2", "d_state": 16, "d_conv": 4,
                     "expand": 2, "headdim": 64, "ngroups": 1,
                     "chunk_size": 32},
         "dtype": "bfloat16", "remat": True}


# the dither cell's limits hold two independent dither draws to the noise
# they show at 8192 tokens: at 128 tokens and 4 values per A_log leaf they
# do not, so its CPU size has 4096 tokens and 32 heads
MEDIUM = dict(SMALL, d_model=128, ssm_cfg=dict(SMALL["ssm_cfg"], headdim=8))
SIZES = {"mamba2-370m.plain": (SMALL, 2, 64),
         "mamba2-370m.dither-kernel": (MEDIUM, 8, 512)}


def small_cell(cell: str) -> tuple[dict, dict]:
    """(configuration, workload) of a cell at its CPU size."""
    config, batch, seq = SIZES[cell]
    wl = json.loads((ROOT / "chipbench" / "workloads" /
                     f"{cell}.json").read_text())
    wl["stream"].update(batch=batch, seq_len=seq)
    return json.loads(json.dumps(config)), wl


@pytest.fixture
def small_config():
    return json.loads(json.dumps(SMALL))
