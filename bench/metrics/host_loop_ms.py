"""Host time of the loop body per raster, outside the input pipeline.

The harness's timer around the ``run_snn`` dispatch and the eager
``reset_dynamics`` (inference: also the wait for the counts on the
host), summed over the window and divided by its rasters.
"""


def read(run: dict) -> float | None:
    w = run["window"]
    return w.host_loop_s / w.rasters * 1e3 if w.rasters else None
