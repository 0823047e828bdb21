"""The single ``shard_map`` call site (lint rule R1).

``core/engine_sharded.py`` shards the engine's weight matrix 2-D over a
(data, model) mesh (docs/architecture.md §8); every ``shard_map`` it runs
goes through :func:`shard_map_compat`, so the call convention lives in
exactly one place.
"""
from __future__ import annotations

import jax


def shard_map_compat(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map``, manual over every mesh axis, with replication
    checking off."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
