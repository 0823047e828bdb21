"""Device meshes for the sharded engine (``core/engine_sharded.py``).

The engine's weight matrix shards 2-D over a (data, model) mesh
(docs/architecture.md §8): one chip on the CPU tests, the 2x2 mesh of a
four-chip v5e host in ``chip_smoke.py --four-chips``.  Building a mesh is a
*function*, so importing this module never touches jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def make_debug_mesh(data: int = 1, model: int = 1) -> Mesh:
    """A (data, model) mesh for CPU tests and the four-chip smoke (device
    count permitting), with every axis ``Auto``.

    ``jax.make_mesh`` defaults to ``Explicit`` axes, under which sharding
    becomes part of every array's type: the engine's history push
    (``dynamic_update_slice`` of an unsharded spike row into a sharded
    plane) is then a type error.  The engine places its arrays with
    ``NamedSharding`` / ``shard_map`` itself, so the partitioner is left
    to propagate everything else.
    """
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
