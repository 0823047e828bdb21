"""plasticity.apply dispatch layer: a third-party rule rides every backend.

The slim-protocol contract (ISSUE 9): a rule defined *outside* the repo —
just a state machine (``init_state``/``step``), a readout, and a magnitude
map, registered through :class:`repro.plasticity.Rank1Rule` — runs
end-to-end on every backend it declares (reference, fused_interpret,
sparse, and across the sharded engine) with zero edits to the engine or
model files, and the backends it does *not* declare fail at config
construction with the registry's pinned messages — never mid-trace.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import EngineConfig, init_engine, run_engine
from repro.launch.mesh import make_debug_mesh
from repro.plasticity import Rank1Rule, register_rule
from repro.plasticity.base import RULES

THIRD_PARTY_BACKENDS = ("reference", "fused_interpret", "sparse")


@dataclasses.dataclass(frozen=True)
class DecayTraceRule(Rank1Rule):
    """Minimal third-party-style rule: a per-neuron decaying uint8 trace.

    Each spike injects 64 into the trace; every step halves it (a shift,
    saturating at 127 so the uint8 word never wraps).  The update
    magnitude is just ``amplitude * trace / 128`` — nothing the built-in
    rules share, so every backend it reaches is reached purely through
    the ``Rank1Rule`` adapters.
    """

    name: str = "thirdparty_trace"

    def init_state(self, n, depth):
        return jnp.zeros((n,), jnp.uint8)

    def step(self, state, spikes, *, depth):
        fired = jnp.asarray(spikes).astype(jnp.uint8)
        return jnp.minimum((state >> 1) + fired * jnp.uint8(64), jnp.uint8(127))

    def readout(self, state):
        return state[None, :]

    def magnitudes_from_readout(self, arr, amplitude, tau, *, depth,
                                pairing="nearest", compensate=True):
        return amplitude * arr[0].astype(jnp.float32) / 128.0

    def last_spikes(self, state):
        return (state >= jnp.uint8(64)).astype(jnp.float32)


@dataclasses.dataclass(frozen=True)
class DenseOnlyRule(DecayTraceRule):
    """Same trace, but declaring the reference datapath only."""

    name: str = "thirdparty_dense"
    has_kernel: bool = False
    has_sparse: bool = False


@pytest.fixture
def third_party_rules():
    full = register_rule(DecayTraceRule())
    dense = register_rule(DenseOnlyRule())
    yield full, dense
    RULES.pop(full.name, None)
    RULES.pop(dense.name, None)


def _run(key, backend, **kw):
    cfg = EngineConfig(n_pre=16, n_post=8, eta=0.25,
                       rule="thirdparty_trace", backend=backend, **kw)
    state = init_engine(key, cfg)
    train = jax.random.bernoulli(key, 0.4, (24, cfg.n_pre))
    final, post = run_engine(state, train, cfg)
    return state, final, post


@pytest.mark.parametrize("backend", THIRD_PARTY_BACKENDS)
def test_third_party_rule_runs_on_declared_backends(key, backend,
                                                    third_party_rules):
    state0, final, post = _run(key, backend)
    w = np.asarray(final.w)
    assert np.isfinite(w).all()
    assert (w >= 0.0).all() and (w <= 1.0).all()
    # the trace actually drives learning — weights move off the init
    assert not np.array_equal(w, np.asarray(state0.w))
    assert final.pre_hist.dtype == jnp.uint8


@pytest.mark.parametrize("backend", ("fused_interpret", "sparse"))
def test_third_party_backends_match_reference(key, backend,
                                              third_party_rules):
    _, ref, post_ref = _run(key, "reference")
    _, got, post_got = _run(key, backend)
    np.testing.assert_allclose(np.asarray(got.w), np.asarray(ref.w),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(post_got), np.asarray(post_ref))


def test_third_party_rule_crosses_sharded_engine(key, third_party_rules):
    from repro.core.engine_sharded import (make_sharded_engine_step,
                                           shard_engine_state)

    cfg = EngineConfig(n_pre=16, n_post=8, eta=0.25,
                       rule="thirdparty_trace", backend="fused_interpret")
    state0 = init_engine(key, cfg)
    train = jax.random.bernoulli(key, 0.4, (16, cfg.n_pre))
    ref_state, ref_post = run_engine(state0, train, cfg)

    mesh = make_debug_mesh(data=1, model=1)
    with mesh:
        st = shard_engine_state(init_engine(key, cfg), mesh)
        step = make_sharded_engine_step(cfg, mesh)
        posts = []
        for t in range(train.shape[0]):
            st, post = step(st, train[t])
            posts.append(np.asarray(post))
    np.testing.assert_allclose(np.asarray(ref_state.w), np.asarray(st.w),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(ref_post), np.stack(posts))


def test_undeclared_backends_fail_at_config_construction(third_party_rules):
    # config-construction errors with the registry's pinned messages —
    # not trace errors from deep inside a backend
    with pytest.raises(ValueError, match="no fused kernel"):
        EngineConfig(rule="thirdparty_dense", backend="fused_interpret")
    with pytest.raises(ValueError, match="no fused kernel"):
        EngineConfig(rule="thirdparty_dense", backend="fused")
    with pytest.raises(ValueError, match="no event-driven"):
        EngineConfig(rule="thirdparty_dense", backend="sparse")


def test_dense_only_rule_runs_on_reference(key, third_party_rules):
    cfg = EngineConfig(n_pre=12, n_post=6, eta=0.25,
                       rule="thirdparty_dense", backend="reference")
    state = init_engine(key, cfg)
    train = jax.random.bernoulli(key, 0.4, (12, cfg.n_pre))
    final, _ = run_engine(state, train, cfg)
    assert np.isfinite(np.asarray(final.w)).all()
