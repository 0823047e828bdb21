"""Spike-encoding data pipeline (paper §IV-B front half).

Chains the synthetic generators with min-max normalisation (eq. 28) and
Bernoulli rate coding (eq. 29) into (T, B, N) spike rasters ready for the
SNN training loop, plus a double-buffered prefetcher so host-side encoding
overlaps device compute.
"""
from __future__ import annotations

import collections
import threading
from functools import partial
from typing import Callable, Iterator

import jax
from jax.profiler import TraceAnnotation, annotate_function

from repro import tracing
from repro.core.encoding import minmax_normalise, rate_code


@partial(annotate_function, name=tracing.ENCODE)
def encode_batch(key: jax.Array, x: jax.Array, t_steps: int) -> jax.Array:
    """(B, ...) floats → (T, B, features) {0,1} spikes.

    Per-sample min-max normalisation (eq. 28) then Bernoulli rate coding
    (eq. 29); feature dims are flattened.
    """
    B = x.shape[0]
    flat = x.reshape(B, -1)
    norm = minmax_normalise(flat, axis=-1)
    return rate_code(key, norm, t_steps)               # (T, B, N)


def spike_stream(key: jax.Array,
                 sampler: Callable[[jax.Array, int], tuple[jax.Array, jax.Array]],
                 *, batch: int, t_steps: int,
                 n_steps: int | None = None) -> Iterator[dict]:
    """Stream of {spikes (T,B,N), labels (B,)} batches from a sampler."""
    step = 0
    while n_steps is None or step < n_steps:
        key, k_data, k_enc = jax.random.split(key, 3)
        with TraceAnnotation(tracing.SAMPLE):
            x, labels = sampler(k_data, batch)
        yield {"spikes": encode_batch(k_enc, x, t_steps), "labels": labels}
        step += 1


class Prefetcher:
    """Double-buffered background prefetch of an iterator (host → device).

    The training loop's `next()` overlaps the *next* batch's generation +
    encoding with the current step's device compute — the standard input-
    pipeline trick, testable on CPU.
    """

    def __init__(self, it: Iterator, depth: int = 2):
        self._it = it
        self._q: collections.deque = collections.deque()
        self._depth = depth
        self._lock = threading.Lock()
        self._done = False
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._fill, daemon=True)
        self._event = threading.Event()
        self._space = threading.Event()
        self._space.set()
        self._thread.start()

    def _fill(self):
        try:
            for item in self._it:
                if self._stop.is_set():
                    return
                while not self._stop.is_set():
                    with self._lock:
                        if len(self._q) < self._depth:
                            with TraceAnnotation(tracing.PREFETCH_PUT):
                                self._q.append(jax.device_put(item))
                            self._event.set()
                            break
                    with TraceAnnotation(tracing.PREFETCH_WAIT_SPACE):
                        self._space.clear()
                        self._space.wait(timeout=0.1)
        finally:
            self._done = True
            self._event.set()

    def close(self, timeout: float = 5.0) -> None:
        """Stop the background thread and drop buffered batches.

        Safe to call at any point — including before the source iterator is
        exhausted (early abandonment: a training loop that stops at an
        accuracy target, or an exception unwinding through the consumer).
        Idempotent; after it returns the fill thread has exited.
        """
        self._stop.set()
        self._space.set()          # unblock a producer waiting for space
        self._thread.join(timeout=timeout)
        with self._lock:
            self._q.clear()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __iter__(self):
        return self

    def __next__(self):
        while True:
            with self._lock:
                if self._q:
                    item = self._q.popleft()
                    self._space.set()
                    return item
                if self._done:
                    raise StopIteration
            with TraceAnnotation(tracing.PREFETCH_WAIT_ITEM):
                self._event.clear()
                self._event.wait(timeout=0.1)
