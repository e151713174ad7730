"""Where the launchers keep JAX's persistent compilation cache."""
import os

import jax
import pytest

from repro.launch import cache


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_dir_is_left_to_jax(monkeypatch, restore_cache_dir, tmp_path):
    monkeypatch.setenv(cache.ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert cache.setup_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_the_fixed_checkout_dir(monkeypatch, restore_cache_dir):
    monkeypatch.delenv(cache.ENV, raising=False)
    assert cache.setup_compile_cache() == cache.REPO_CACHE
    assert jax.config.jax_compilation_cache_dir == cache.REPO_CACHE
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert cache.REPO_CACHE == os.path.join(root, ".jax_cache")
