"""Device time of the forward pass per raster.

The union of the traced slice's device intervals of ops under the
program's ``snn.forward`` scope (patches, the synaptic contraction,
inhibition, the neuron step, WTA, the threshold update, pooling), divided
by the slice's rasters.
"""
from metrics import _scopes


def read(run: dict) -> float | None:
    return _scopes.read(run, _scopes.FORWARD)
