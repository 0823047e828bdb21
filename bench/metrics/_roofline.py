"""Shared reduction of a kernel's roofline share from the trace.

share = steps done in the traced slice * least time of one step's
updates of those layers / summed device time of the kernel's events.
The steps are the slice's rasters times the traffic's steps per raster,
so the count follows the work and not how the kernel is split into
calls.  The least time is the larger of operations over peak FLOP/s and
bytes over peak bandwidth, both from logical shapes (``costs/snn.py``).
No events: no reading.
"""
from trace_reduce import kernel_events


def share(run: dict, pattern: str, kinds: tuple) -> tuple[float, str] | None:
    t = run["trace"]
    if t is None:
        return None
    ev = kernel_events(t["ops"], pattern)
    tr = run["traffic"]
    ls = [l for l in run["costs"].layers(run["config"], tr["batch"]) if l["kind"] in kinds]
    steps = run["window"].rasters * tr["t_steps"]
    if not ev or not ls or not steps:
        return None
    busy = sum(e - s for _, s, e in ev) / 1e9
    t_min, bound = run["costs"].update_min_seconds(ls, run["peak"])
    return 100.0 * steps * t_min / busy, bound
