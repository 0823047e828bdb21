"""Device time of the timing state per raster.

The union of the traced slice's device intervals of ops under the
program's ``stdp.timing`` scope (the spike-history pushes, and every
readout of them for the update kernels: word packing, the per-sample
views, the conv im2col of words), divided by the slice's rasters.
"""
from metrics import _scopes


def read(run: dict) -> float | None:
    return _scopes.read(run, _scopes.TIMING)
