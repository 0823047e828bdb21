"""Placement of JAX's persistent compilation cache for the entry points.

Each entry point calls :func:`enable_compile_cache` from its ``main()``;
nothing here runs at import, so tests and library callers keep whatever
cache configuration they set themselves.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# A fixed path inside the checkout: the cache directory is part of what
# a later run must match to find its entries again, so it never carries a
# temp name, a pid or a time.
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compile cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    other directory is set here.  Otherwise the cache goes to
    ``<checkout>/.jax_cache``.

    Entries are keyed with the ops' metadata.  It carries the device
    scopes (``repro.tracing``) that a profile reads; a key without it would
    hand back an executable compiled from other source, with that source's
    scopes and instruction names.
    """
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
