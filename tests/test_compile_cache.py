"""Placement of the persistent compile cache by the entry points."""
from pathlib import Path

import jax
import pytest

from repro.launch.compile_cache import CHECKOUT_CACHE_DIR, enable_compile_cache


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    keyed = jax.config.jax_compilation_cache_include_metadata_in_key
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", keyed)


def test_env_dir_is_left_to_jax(monkeypatch, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    jax.config.update("jax_compilation_cache_dir", None)
    assert enable_compile_cache() == "/elsewhere/cache"
    # the helper sets no directory of its own
    assert jax.config.jax_compilation_cache_dir is None


def test_default_is_fixed_dir_in_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    root = Path(__file__).resolve().parents[1]
    assert CHECKOUT_CACHE_DIR == root / ".jax_cache"
    assert enable_compile_cache() == str(root / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == str(root / ".jax_cache")


def test_entries_are_keyed_with_op_metadata(monkeypatch, restore_cache_dir):
    """The device scopes live in op metadata: a cache key without it would
    return an executable with another source's scopes."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", False)
    enable_compile_cache()
    assert jax.config.jax_compilation_cache_include_metadata_in_key
