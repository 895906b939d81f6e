"""Where the launchers put JAX's persistent compilation cache."""
from pathlib import Path

import jax

from repro.launch.compile_cache import enable_compile_cache


def test_env_dir_is_left_to_jax(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/cache/from/env")
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == "/cache/from/env"
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_fixed_in_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        got = enable_compile_cache()
        # <checkout>/tests/this_file -> <checkout>/.jax_cache
        want = Path(__file__).resolve().parents[1] / ".jax_cache"
        assert got == str(want)
        assert jax.config.jax_compilation_cache_dir == got
        assert enable_compile_cache() == got  # same path on every call
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
