import os

import jax

from repro import compile_cache
from repro.compile_cache import enable_compilation_cache

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def test_environment_places_the_cache(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    before = jax.config.jax_compilation_cache_dir
    assert enable_compilation_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_default_cache_is_a_fixed_path_in_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = enable_compilation_cache()
        assert path == os.path.join(ROOT, ".jax_cache")
        assert path == compile_cache.DEFAULT_DIR
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
