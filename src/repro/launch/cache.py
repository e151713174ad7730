"""Where JAX keeps its persistent compilation cache.

The cache key includes the directory, so the directory must not move
between runs: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it
(JAX reads the variable itself), otherwise ``<checkout>/.jax_cache``.
Call :func:`setup_compile_cache` before the first compile.
"""
from __future__ import annotations

import os

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def setup_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory and
    return that directory."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE)
    return REPO_CACHE
