"""Persistent XLA compilation cache at a directory chosen from outside.

JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself; when it is set, this module
sets nothing.  Otherwise the cache goes to ``<checkout>/.jax_cache``.  The
path is fixed on purpose: the directory is part of what a later run has to
find again, so it is never built from a temporary name, a pid or a time.

    from repro.compile_cache import enable_compilation_cache
    enable_compilation_cache()      # before the first jit compiles
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", ".jax_cache"))


def enable_compilation_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
