"""Architecture registry: --arch <id> resolves here."""
from __future__ import annotations

import importlib
from typing import Dict

from repro.configs.shapes import SHAPES, ShapeCase, applicable  # noqa: F401

_MODULES: Dict[str, str] = {
    "qwen2.5-32b": "repro.configs.qwen2_5_32b",
    "gemma-2b": "repro.configs.gemma_2b",
    "gemma3-4b": "repro.configs.gemma3_4b",
    "minitron-8b": "repro.configs.minitron_8b",
    "dbrx-132b": "repro.configs.dbrx_132b",
    "moonshot-v1-16b-a3b": "repro.configs.moonshot_v1_16b_a3b",
    "hymba-1.5b": "repro.configs.hymba_1_5b",
    "mamba2-370m": "repro.configs.mamba2_370m",
    "internvl2-2b": "repro.configs.internvl2_2b",
    "whisper-small": "repro.configs.whisper_small",
}

ARCH_IDS = tuple(_MODULES)


def get_model(arch_id: str):
    """Full-size (published) config.

    Configs whose params, optimizer state and activations fit one chip run
    on it (mamba2-370m trains on one 16 GB v5e; see ``chip_smoke.py``);
    the larger ones are materialized only on a sharded deployment and are
    otherwise compiled by the dry-run from shapes alone.
    """
    return importlib.import_module(_MODULES[arch_id]).config()


def get_smoke_model(arch_id: str):
    """Reduced same-family config for CPU smoke tests."""
    return importlib.import_module(_MODULES[arch_id]).smoke()
