"""Host time the loop waits on the input pipeline, per raster.

The harness's timer around ``next(stream)`` (training: the prefetched
``spike_stream``) or around the sampler and ``encode_batch``
(inference), summed over the window and divided by its rasters.
"""


def read(run: dict) -> float | None:
    w = run["window"]
    return w.data_wait_s / w.rasters * 1e3 if w.rasters else None
