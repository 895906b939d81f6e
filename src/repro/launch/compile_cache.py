"""Where JAX's persistent compilation cache lives for the launchers.

Called from the ``main()`` of each launcher and from ``chip_smoke.py``,
never at import time, so library users and the test suite keep JAX's own
defaults.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/src/repro/launch/compile_cache.py -> <checkout>/.jax_cache
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here. Otherwise the cache goes to a fixed
    ``<checkout>/.jax_cache``: the path is part of the cache key, so it
    never depends on a temp name, a pid or a time.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
