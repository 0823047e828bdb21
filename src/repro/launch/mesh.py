"""Production mesh definitions.

``make_production_mesh`` is a *function* so importing this module never
touches jax device state (device count is locked at first jax init; the
dry-run sets ``XLA_FLAGS=--xla_force_host_platform_device_count=512``
before any jax import to get placeholder devices).

Geometry (DESIGN.md §6):
  * single-pod: (data=16, model=16)            — 256 chips (one v5e pod)
  * multi-pod : (pod=2, data=16, model=16)     — 512 chips across 2 pods;
    the ``pod`` axis carries pure data parallelism over the slower
    inter-pod links (po2-compressed gradient exchange).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def _auto_mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> Mesh:
    """``jax.make_mesh`` with every axis ``Auto``.

    ``jax.make_mesh`` defaults to ``Explicit`` axes, under which sharding
    becomes part of every array's type: the engine's history push
    (``dynamic_update_slice`` of an unsharded spike row into a sharded
    plane) is then a type error.  The engine and the LM step place their
    arrays with ``NamedSharding`` / ``shard_map`` themselves, so the
    partitioner is left to propagate everything else.
    """
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_debug_mesh(data: int = 1, model: int = 1, pod: int | None = None
                    ) -> Mesh:
    """Small meshes for CPU tests and the four-chip smoke (device count
    permitting)."""
    if pod is not None:
        return _auto_mesh((pod, data, model), ("pod", "data", "model"))
    return _auto_mesh((data, model), ("data", "model"))


def describe(mesh: Mesh) -> str:
    return " × ".join(f"{n}={s}" for n, s in zip(mesh.axis_names,
                                                 mesh.devices.shape))
