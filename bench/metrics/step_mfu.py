"""The whole step's share of the chip's bf16 peak.

Operations of every simulation step of every raster completed in the
window (forward current, and when training the STDP update, counted from
logical shapes in ``costs/snn.py``) over the window's seconds and the
peak of ``peaks.json``.
"""


def read(run: dict) -> float | None:
    w = run["window"]
    if not w.rasters:
        return None
    tr = run["traffic"]
    flops = run["costs"].step_flops(run["config"], tr["batch"], tr["mode"] == "train")
    done = flops * tr["t_steps"] * w.rasters
    return 100.0 * done / w.seconds / run["peak"]["bf16_flops_per_s"]
