"""Roofline share of the packed fc update kernel (``kernels/itp_stdp``).

Device time of the trace events named after the kernel's entry point
``itp_stdp_update_packed`` (one per simulation step and fc layer, the
batch mapped inside it), against the least time the fc layers' updates
need by their logical shapes.  The batch sum that follows the kernel is
not part of it.
"""
from metrics._roofline import share

KERNEL = r"itp_stdp_update_packed"


def read(run: dict) -> float | None:
    got = share(run, KERNEL, ("fc",))
    return None if got is None else got[0]
