"""Roofline share of the packed conv update kernel (``kernels/itp_stdp_conv``).

Device time of the trace events named after the kernel's entry point
``itp_stdp_conv_delta_packed`` (one per simulation step and conv layer,
1-D and 2-D alike), against the least time the conv layers' updates
need, counted from the word and spike planes before any im2col gather.
"""
from metrics._roofline import share

KERNEL = r"itp_stdp_conv_delta_packed"


def read(run: dict) -> float | None:
    got = share(run, KERNEL, ("conv1d", "conv2d"))
    return None if got is None else got[0]
