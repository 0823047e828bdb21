from repro.data.synthetic import (synthetic_digits, synthetic_fashion,
                                  synthetic_fault)
from repro.data.pipeline import Prefetcher, encode_batch, spike_stream

__all__ = [
    "synthetic_digits", "synthetic_fashion", "synthetic_fault",
    "Prefetcher", "encode_batch", "spike_stream",
]
