"""Device time of the weight update per raster.

The union of the traced slice's device intervals of ops under the
program's ``stdp.update`` scope and in no inner scope (the update kernels,
their lane padding, the batch sum, the clip and the quantisation), divided
by the slice's rasters.  Training cells only.
"""
from metrics import _scopes


def read(run: dict) -> float | None:
    return _scopes.read(run, _scopes.UPDATE)
