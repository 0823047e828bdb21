"""Pluggable weight-update backend: fused Pallas path ≡ reference path.

The engine's step-3 datapath is selectable via ``EngineConfig.backend``
(and ``SNNConfig.backend`` at the network level).  These tests pin the
contract every later scaling PR relies on: ``fused_interpret`` (the Pallas
kernel run through the interpreter, i.e. the exact kernel semantics) tracks
``reference`` within float tolerance over long multi-step scans, including
the quantised-weight path and both pairing modes.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.core.engine import (EngineConfig, init_engine,
                               init_engine_population, run_engine,
                               run_engine_population)
from repro.models import snn

T_STEPS = 64


def _run_pair(key, cfg_ref, t_steps=T_STEPS):
    cfg_fused = dataclasses.replace(cfg_ref, backend="fused_interpret")
    state = init_engine(key, cfg_ref)
    train = jax.random.bernoulli(key, 0.35, (t_steps, cfg_ref.n_pre))
    s_ref, post_ref = run_engine(state, train, cfg_ref)
    s_fused, post_fused = run_engine(state, train, cfg_fused)
    return s_ref, post_ref, s_fused, post_fused


@pytest.mark.parametrize("quantise", [False, True])
@pytest.mark.parametrize("n_pre,n_post", [(32, 24), (130, 70)])
def test_fused_matches_reference_over_scan(key, quantise, n_pre, n_post):
    cfg = EngineConfig(n_pre=n_pre, n_post=n_post, eta=0.25,
                       quantise=quantise)
    s_ref, post_ref, s_fused, post_fused = _run_pair(key, cfg)
    np.testing.assert_allclose(np.asarray(s_fused.w), np.asarray(s_ref.w),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(post_fused),
                                  np.asarray(post_ref))


@pytest.mark.parametrize("pairing", ["nearest", "all"])
def test_fused_matches_reference_both_pairings(key, pairing):
    cfg = EngineConfig(n_pre=48, n_post=48, pairing=pairing, eta=0.5)
    s_ref, _, s_fused, _ = _run_pair(key, cfg)
    np.testing.assert_allclose(np.asarray(s_fused.w), np.asarray(s_ref.w),
                               atol=1e-5, rtol=1e-5)


def test_population_backend_equivalence(key):
    """vmapped replicas take the kernel path identically to the loop."""
    cfg = EngineConfig(n_pre=40, n_post=32, quantise=True)
    cfg_fused = dataclasses.replace(cfg, backend="fused_interpret")
    states = init_engine_population(key, cfg, 3)
    trains = jax.random.bernoulli(key, 0.3, (3, T_STEPS, cfg.n_pre))
    s_ref, post_ref = run_engine_population(states, trains, cfg)
    s_fused, post_fused = run_engine_population(states, trains, cfg_fused)
    assert post_ref.shape == (3, T_STEPS, cfg.n_post)
    np.testing.assert_allclose(np.asarray(s_fused.w), np.asarray(s_ref.w),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(post_fused),
                                  np.asarray(post_ref))


def test_population_replicas_are_independent(key):
    """Per-replica keys give distinct initial weights (no broadcast bug)."""
    cfg = EngineConfig(n_pre=16, n_post=16)
    states = init_engine_population(key, cfg, 4)
    assert states.w.shape == (4, 16, 16)
    flat = np.asarray(states.w).reshape(4, -1)
    assert not np.allclose(flat[0], flat[1])


def test_packed_engine_trajectory_bit_identical_to_unpacked(key):
    """Multi-step engine scan: the packed uint8 word datapath (the default
    fused storage format) is bit-identical to the unpacked bitplane kernel
    datapath — array_equal over the full trajectory, both pairings."""
    for pairing in ("nearest", "all"):
        cfg_packed = EngineConfig(n_pre=48, n_post=40, eta=0.25,
                                  pairing=pairing, backend="fused_interpret")
        cfg_unpacked = dataclasses.replace(cfg_packed, packed_history=False)
        assert cfg_packed.packed_history          # packed is the default
        state = init_engine(key, cfg_packed)
        train = jax.random.bernoulli(key, 0.35, (T_STEPS, 48))
        s_p, post_p = run_engine(state, train, cfg_packed)
        s_u, post_u = run_engine(state, train, cfg_unpacked)
        np.testing.assert_array_equal(np.asarray(s_p.w), np.asarray(s_u.w))
        np.testing.assert_array_equal(np.asarray(post_p), np.asarray(post_u))


def test_packed_snn_fc_trajectory_bit_identical_to_unpacked(key):
    """Network-level fc path: packed words ≡ unpacked bitplanes, bit for bit."""
    cfg_packed = snn.mnist_2layer("itp", n_hidden=24,
                                  backend="fused_interpret")
    cfg_unpacked = dataclasses.replace(cfg_packed, packed_history=False)
    batch, t = 4, 10
    state = snn.init_snn(key, cfg_packed, batch)
    raster = jax.random.bernoulli(key, 0.2, (t, batch, 28 * 28))
    s_p, counts_p = snn.run_snn(state, raster, cfg_packed, train=True)
    s_u, counts_u = snn.run_snn(state, raster, cfg_unpacked, train=True)
    np.testing.assert_array_equal(np.asarray(s_p.weights[0]),
                                  np.asarray(s_u.weights[0]))
    np.testing.assert_array_equal(np.asarray(counts_p), np.asarray(counts_u))


def _pallas_calls(jaxpr):
    """Every ``pallas_call`` equation in ``jaxpr`` and its sub-jaxprs."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _pallas_calls(sub)
    return found


def test_fc_delta_is_one_batch_contracting_kernel_call(key):
    """The fc update hands the whole batch to one kernel call whose only
    output is the padded (K, C) Δw tile — no per-sample tiles to sum."""
    from repro.plasticity.apply import make_plan
    cfg = snn.mnist_2layer("itp", backend="fused_interpret")   # 784 -> 100
    batch = 16
    layer = snn.init_snn(key, cfg, batch).layers[0]
    s_in = jax.random.bernoulli(key, 0.2, (batch, 784)).astype(np.float32)
    s_out = jax.random.bernoulli(key, 0.2, (batch, 100)).astype(np.float32)
    closed = jax.make_jaxpr(make_plan(cfg).fc_delta)(
        layer.pre_hist, layer.post_hist, s_in, s_out)
    (call,) = _pallas_calls(closed.jaxpr)
    assert [v.aval.shape for v in call.outvars] == [(896, 128)]


def test_depth_beyond_word_width_falls_back_to_unpacked(key):
    """depth > 8 exceeds the packed uint8 word; the fused path must keep
    running on the unpacked bitplane operands (previously-working configs
    stay working) and still match the reference trajectory."""
    cfg = EngineConfig(n_pre=24, n_post=16, depth=9, eta=0.25)
    cfg_fused = dataclasses.replace(cfg, backend="fused_interpret")
    assert cfg_fused.packed_history and not cfg_fused.use_packed_history()
    state = init_engine(key, cfg)
    train = jax.random.bernoulli(key, 0.35, (32, cfg.n_pre))
    s_ref, post_ref = run_engine(state, train, cfg)
    s_fused, post_fused = run_engine(state, train, cfg_fused)
    np.testing.assert_allclose(np.asarray(s_fused.w), np.asarray(s_ref.w),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(post_fused),
                                  np.asarray(post_ref))


def test_unknown_backend_rejected():
    with pytest.raises(ValueError, match="unknown backend"):
        EngineConfig(backend="cuda")
    with pytest.raises(ValueError, match="unknown backend"):
        snn.mnist_2layer("itp", n_hidden=8, backend="nope")


def test_snn_fc_backend_equivalence(key):
    """Network-level fused fc update ≡ reference einsum update."""
    cfg_ref = snn.mnist_2layer("itp", n_hidden=24)
    cfg_fused = dataclasses.replace(cfg_ref, backend="fused_interpret")
    batch, t = 4, 10
    state = snn.init_snn(key, cfg_ref, batch)
    raster = jax.random.bernoulli(key, 0.2, (t, batch, 28 * 28))
    s_ref, counts_ref = snn.run_snn(state, raster, cfg_ref, train=True)
    s_fused, counts_fused = snn.run_snn(state, raster, cfg_fused, train=True)
    np.testing.assert_allclose(np.asarray(s_fused.weights[0]),
                               np.asarray(s_ref.weights[0]),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(counts_fused),
                                  np.asarray(counts_ref))


def test_launcher_engine_mode_smoke():
    """The launch-path engine workload runs end-to-end on the kernel path."""
    import argparse

    from repro.launch.train import run_engine_training

    args = argparse.Namespace(rule="itp", backend="fused_interpret",
                              engine_pre=32, engine_post=32, replicas=2,
                              steps=8, engine_rate=0.3)
    summary = run_engine_training(args)
    assert summary["rule"] == "itp"
    assert summary["backend"] == "fused_interpret"
    assert summary["sops_per_s"] > 0
    assert np.isfinite(summary["mean_post_rate"])
