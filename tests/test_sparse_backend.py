"""Event-driven sparse backend parity: ops, engine scan, sharded, SNNs.

The sparse datapath must be *exactly* the dense reference wherever the
event lists are uncapped — the scatter-RMW sequence touches only the
slices the XOR pair gate could have made non-zero — and deterministically
truncated (highest-indexed events dropped) when ``max_events`` caps the
lists.  Pinned at every level the backend routes through:

  * ops:        ``sparse_weight_update`` / ``sparse_synapse_delta`` vs
                the dense ``repro.core.stdp`` formulas
  * engine:     jitted ``run_engine`` scan trajectories vs reference
  * sharded:    ``make_sharded_engine_step`` on a 1×1 mesh vs reference
  * networks:   2layer-SNN / DCSNN / CSNN full-trajectory parity
  * launcher:   ``repro.launch.train`` engine + snn modes run end-to-end
"""

import argparse
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import EngineConfig, init_engine, run_engine
from repro.core.stdp import STDPParams, magnitudes_depth_major, synapse_update
from repro.kernels.itp_sparse.events import spike_events
from repro.kernels.itp_sparse.ops import sparse_synapse_delta, sparse_weight_update
from repro.launch.mesh import make_debug_mesh
from repro.models import snn

DEPTH = 7


def _rand_case(key, n_pre=12, n_post=9, density=0.4):
    ks = jax.random.split(key, 5)
    w = jax.random.uniform(ks[0], (n_pre, n_post), minval=0.2, maxval=0.8)
    pre = jax.random.bernoulli(ks[1], density, (n_pre,)).astype(jnp.float32)
    post = jax.random.bernoulli(ks[2], density, (n_post,)).astype(jnp.float32)
    pre_h = jax.random.bernoulli(ks[3], 0.3, (n_pre, DEPTH)).astype(jnp.float32)
    post_h = jax.random.bernoulli(ks[4], 0.3, (n_post, DEPTH)).astype(jnp.float32)
    return w, pre, post, pre_h, post_h


def _magnitudes(hist_nd, amplitude, tau, pairing):
    return magnitudes_depth_major(hist_nd.T, amplitude, tau, pairing=pairing, compensate=True)


# ---------------------------------------------------------------------------
# Ops level
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("pairing", ["nearest", "all"])
@pytest.mark.parametrize("density", [0.05, 0.4, 1.0])
def test_sparse_weight_update_matches_dense(key, pairing, density):
    p = STDPParams()
    w, pre, post, pre_h, post_h = _rand_case(key, density=density)
    dense = synapse_update(w, pre, post, pre_h, post_h, p, pairing=pairing, eta=1 / 16)
    ltp = _magnitudes(pre_h, p.a_plus, p.tau_plus, pairing)
    ltd = _magnitudes(post_h, p.a_minus, p.tau_minus, pairing)
    sparse = sparse_weight_update(w, pre, post, ltp, ltd, eta=1 / 16)
    np.testing.assert_allclose(np.asarray(sparse), np.asarray(dense), rtol=1e-6, atol=1e-7)


def test_sparse_synapse_delta_matches_dense_formula(key):
    p = STDPParams()
    _, pre, post, pre_h, post_h = _rand_case(key)
    ltp = _magnitudes(pre_h, p.a_plus, p.tau_plus, "nearest")
    ltd = _magnitudes(post_h, p.a_minus, p.tau_minus, "nearest")
    ltp_term = (1.0 - pre[:, None]) * ltp[:, None] * post[None, :]
    ltd_term = pre[:, None] * (1.0 - post[None, :]) * ltd[None, :]
    want = ltp_term - ltd_term
    got = sparse_synapse_delta(pre, post, ltp, ltd)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-7)


def test_sparse_update_overflow_truncates_highest_indices(key):
    """Capped lists keep the first ``max_events`` active indices: the
    update equals the dense formula with the dropped (highest-indexed)
    spikes masked OUT of the scatter sides but still present in the
    magnitudes' pair gate."""
    p = STDPParams()
    cap = 2
    w, pre, post, pre_h, post_h = _rand_case(key, density=0.9)
    ltp = _magnitudes(pre_h, p.a_plus, p.tau_plus, "nearest")
    ltd = _magnitudes(post_h, p.a_minus, p.tau_minus, "nearest")

    def trunc(spikes):
        idx, _ = spike_events(spikes, cap)
        kept = jnp.zeros_like(spikes).at[idx].set(1.0, mode="drop")
        return spikes * kept

    pre_t, post_t = trunc(pre), trunc(post)
    ltp_term = (1.0 - pre[:, None]) * ltp[:, None] * post_t[None, :]
    ltd_term = pre_t[:, None] * (1.0 - post[None, :]) * ltd[None, :]
    want = jnp.clip(w + (1 / 16) * (ltp_term - ltd_term), 0.0, 1.0)
    got = sparse_weight_update(w, pre, post, ltp, ltd, eta=1 / 16, max_events=cap)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-7)


# ---------------------------------------------------------------------------
# Engine scan level
# ---------------------------------------------------------------------------


def _run_engine_pair(
    key,
    backend,
    *,
    rule="itp",
    pairing="nearest",
    quantise=False,
    density=0.35,
    max_events=None,
    t=48,
):
    cfg = EngineConfig(
        n_pre=24,
        n_post=16,
        rule=rule,
        backend=backend,
        pairing=pairing,
        quantise=quantise,
        max_events=max_events,
    )
    state = init_engine(key, cfg)
    spike_key = jax.random.fold_in(key, 7)
    train = jax.random.bernoulli(spike_key, density, (t, cfg.n_pre)).astype(jnp.float32)
    return run_engine(state, train, cfg)


@pytest.mark.parametrize("pairing", ["nearest", "all"])
@pytest.mark.parametrize("quantise", [False, True])
def test_engine_sparse_matches_reference(key, pairing, quantise):
    for density in (0.02, 0.3, 0.9):
        ref_st, ref_post = _run_engine_pair(
            key, "reference", pairing=pairing, quantise=quantise, density=density
        )
        sp_st, sp_post = _run_engine_pair(
            key, "sparse", pairing=pairing, quantise=quantise, density=density
        )
        np.testing.assert_allclose(np.asarray(ref_st.w), np.asarray(sp_st.w), rtol=1e-5, atol=1e-6)
        np.testing.assert_array_equal(np.asarray(ref_post), np.asarray(sp_post))


def test_engine_sparse_itp_nocomp_matches_reference(key):
    ref_st, ref_post = _run_engine_pair(key, "reference", rule="itp_nocomp")
    sp_st, sp_post = _run_engine_pair(key, "sparse", rule="itp_nocomp")
    np.testing.assert_allclose(np.asarray(ref_st.w), np.asarray(sp_st.w), rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(ref_post), np.asarray(sp_post))


def test_engine_sparse_silent_raster_is_noop(key):
    cfg = EngineConfig(n_pre=8, n_post=6, backend="sparse")
    state = init_engine(key, cfg)
    train = jnp.zeros((20, cfg.n_pre))
    out, post = run_engine(state, train, cfg)
    np.testing.assert_array_equal(np.asarray(out.w), np.asarray(state.w))
    assert not np.asarray(post).any()


def test_engine_sparse_capped_is_deterministic_and_bounded(key):
    a_st, a_post = _run_engine_pair(key, "sparse", density=0.8, max_events=3)
    b_st, b_post = _run_engine_pair(key, "sparse", density=0.8, max_events=3)
    np.testing.assert_array_equal(np.asarray(a_st.w), np.asarray(b_st.w))
    np.testing.assert_array_equal(np.asarray(a_post), np.asarray(b_post))
    w = np.asarray(a_st.w)
    assert np.isfinite(w).all() and (w >= 0.0).all() and (w <= 1.0).all()


def test_engine_max_events_validation():
    with pytest.raises(ValueError, match="max_events"):
        EngineConfig(max_events=0)
    with pytest.raises(ValueError, match="max_events"):
        EngineConfig(max_events=-3)
    EngineConfig(max_events=1)  # valid
    EngineConfig(max_events=None)  # uncapped


# ---------------------------------------------------------------------------
# Sharded engine level (1×1 mesh on the single CPU device)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("max_events", [None, 5])
def test_sharded_engine_sparse_parity_single_device(key, max_events):
    from repro.core.engine_sharded import make_sharded_engine_step, shard_engine_state

    cfg = EngineConfig(n_pre=24, n_post=16, backend="sparse", max_events=max_events)
    state = init_engine(key, cfg)
    t = 40
    spike_key = jax.random.fold_in(key, 7)
    train = jax.random.bernoulli(spike_key, 0.3, (t, cfg.n_pre)).astype(jnp.float32)
    mesh = make_debug_mesh(data=1, model=1)
    with mesh:
        st = shard_engine_state(state, mesh)
        step = make_sharded_engine_step(cfg, mesh)
        posts = []
        for i in range(t):
            st, p = step(st, train[i])
            posts.append(np.asarray(p))
    ref_st, ref_post = run_engine(state, train, cfg)
    np.testing.assert_allclose(np.asarray(ref_st.w), np.asarray(st.w), rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(ref_post), np.stack(posts))


# ---------------------------------------------------------------------------
# Network level: the paper's three SNNs
# ---------------------------------------------------------------------------


def _snn_cfg(maker, shape, backend, **kw):
    cfg = maker("itp", **kw)
    return dataclasses.replace(cfg, input_shape=shape, backend=backend)


def _run_snn(cfg, shape, t=10, batch=2, rate=0.25):
    state = snn.init_snn(jax.random.PRNGKey(1), cfg, batch)
    raster_key = jax.random.PRNGKey(3)
    raster = jax.random.bernoulli(raster_key, rate, (t, batch) + shape).astype(jnp.float32)
    return snn.run_snn(state, raster, cfg, train=True)


@pytest.mark.parametrize(
    "maker,shape,kw",
    [
        (snn.mnist_2layer, (14, 14, 1), {"n_hidden": 30}),
        (snn.fmnist_dcsnn, (12, 12, 1), {}),
        (snn.fault_csnn, (64, 2), {"length": 64}),
    ],
    ids=["2layer", "dcsnn", "csnn"],
)
def test_snn_sparse_matches_reference(maker, shape, kw):
    ref_st, ref_out = _run_snn(_snn_cfg(maker, shape, "reference", **kw), shape)
    sp_st, sp_out = _run_snn(_snn_cfg(maker, shape, "sparse", **kw), shape)
    for wr, ws in zip(ref_st.weights, sp_st.weights):
        np.testing.assert_allclose(np.asarray(wr), np.asarray(ws), rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(ref_out), np.asarray(sp_out))


def test_snn_sparse_capped_is_deterministic():
    shape = (14, 14, 1)
    cfg = _snn_cfg(snn.mnist_2layer, shape, "sparse", n_hidden=30)
    cfg = dataclasses.replace(cfg, max_events=8)
    a, _ = _run_snn(cfg, shape)
    b, _ = _run_snn(cfg, shape)
    for wa, wb in zip(a.weights, b.weights):
        np.testing.assert_array_equal(np.asarray(wa), np.asarray(wb))
        assert np.isfinite(np.asarray(wa)).all()


def test_snn_max_events_validation():
    with pytest.raises(ValueError, match="max_events"):
        snn.mnist_2layer("itp", backend="sparse", max_events=0)
    snn.mnist_2layer("itp", backend="sparse", max_events=4)  # valid


# ---------------------------------------------------------------------------
# Launcher level
# ---------------------------------------------------------------------------


def test_launcher_engine_mode_sparse_smoke():
    from repro.launch.train import run_engine_training

    ns = argparse.Namespace(
        rule="itp",
        backend="sparse",
        engine_pre=32,
        engine_post=32,
        replicas=2,
        steps=8,
        engine_rate=0.3,
        max_events=8,
    )
    summary = run_engine_training(ns)
    assert summary["backend"] == "sparse"
    assert summary["sops_per_s"] > 0


def test_launcher_snn_mode_sparse_smoke():
    from repro.launch.train import run_snn_training

    ns = argparse.Namespace(
        rule="itp",
        backend="sparse",
        snn="2layer-snn",
        steps=4,
        batch=2,
        engine_rate=0.3,
        max_events=None,
    )
    summary = run_snn_training(ns)
    assert summary["backend"] == "sparse"
