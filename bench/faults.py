"""Faults planted underneath the timed path, to show the check sees them.

Each is a context manager that patches the program for its duration
(and clears JAX's caches on the way in and out, so the jitted step is
traced again with the fault in it):

  * ``unchanged``: a training step returns the weights it was given;
  * ``half_batch``: the update uses the first half of the batch and
    takes the mean over it (training); the second half of the answers
    repeats the first (inference);
  * ``altered``: the first sample's answer (its last-layer spike counts)
    is replaced by the second sample's where ``run_snn`` produces it.

The exchange between chips has no fault here: every cell runs on one chip.
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

FAULTS = ("unchanged", "half_batch", "altered")


def _half(x: jax.Array) -> jax.Array:
    keep = (jnp.arange(x.shape[0]) < x.shape[0] // 2).astype(x.dtype)
    return x * keep.reshape((-1,) + (1,) * (x.ndim - 1))


@contextlib.contextmanager
def planted(name: str):
    from repro.models import snn
    from repro.plasticity.apply import UpdatePlan

    if name not in FAULTS:
        raise ValueError(f"unknown fault {name!r}; known: {FAULTS}")
    run_snn, fc_delta, conv_delta = snn.run_snn, UpdatePlan.fc_delta, UpdatePlan.conv_delta

    def run_faulty(state, raster, cfg, *, train=True):
        new, counts = run_snn(state, raster, cfg, train=train)
        if name == "unchanged" and train:
            new = new._replace(weights=state.weights)
        if name == "altered":
            counts = counts.at[0].set(counts[1])
        if name == "half_batch" and not train:
            h = counts.shape[0] // 2
            counts = counts.at[h:2 * h].set(counts[:h])
        return new, counts

    def fc_half(self, pre_state, post_state, s_in, s_out):
        return 2.0 * fc_delta(self, pre_state, post_state, _half(s_in), _half(s_out))

    def conv_half(self, pre_state, post_state, patches, s_out, **kw):
        return 2.0 * conv_delta(self, pre_state, post_state, _half(patches), _half(s_out), **kw)

    jax.clear_caches()
    snn.run_snn = run_faulty
    if name == "half_batch":
        UpdatePlan.fc_delta, UpdatePlan.conv_delta = fc_half, conv_half
    try:
        yield
    finally:
        snn.run_snn = run_snn
        UpdatePlan.fc_delta, UpdatePlan.conv_delta = fc_delta, conv_delta
        jax.clear_caches()
