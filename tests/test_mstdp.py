"""Reward-modulated ITP-STDP (rule="mstdp") rides every backend for free.

The ISSUE-9 payoff test: mstdp is written against the slim
:class:`Rank1Rule` protocol only (state machine + readout + modulated
magnitudes — ~100 LoC, no kernel code), yet runs on reference /
fused_interpret / sparse, through the sharded engine and the
train-to-accuracy trainer, with zero edits to the engine or model files.
Also pins the eligibility-word arithmetic (shift decay, saturation, the
/128 fixed-point read) and the reward semantics (r=0 freezes learning,
r<0 flips the update direction).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import history as H
from repro.core.engine import EngineConfig, init_engine, run_engine
from repro.launch.mesh import make_debug_mesh
from repro.plasticity import MSTDP, MSTDPRule, MSTDPState, get_rule
from repro.plasticity.base import RULES
from repro.plasticity.mstdp import ELIG_INJECT, ELIG_MAX

MSTDP_BACKENDS = ("reference", "fused_interpret", "sparse")
T_STEPS = 32


def _run(key, backend, **kw):
    cfg = EngineConfig(n_pre=16, n_post=8, rule="mstdp", backend=backend, **kw)
    state = init_engine(key, cfg)
    train = jax.random.bernoulli(key, 0.3, (T_STEPS, cfg.n_pre))
    final, post = run_engine(state, train, cfg)
    return state, final, post


@pytest.fixture
def reward(request):
    """Temporarily re-register mstdp with a different reward scalar."""
    RULES["mstdp"] = MSTDPRule(reward=request.param)
    yield request.param
    RULES["mstdp"] = MSTDP


# ---------------------------------------------------------------------------
# State machine: the eligibility word
# ---------------------------------------------------------------------------


def test_eligibility_word_shift_decay_and_saturation():
    rule = get_rule("mstdp")
    state = rule.init_state(4, 7)
    assert isinstance(state, MSTDPState)
    assert state.elig.dtype == jnp.uint8
    ones = jnp.ones((4,), jnp.float32)
    # repeated spiking saturates at ELIG_MAX and never wraps the word
    for _ in range(10):
        state = rule.step(state, ones, depth=7)
    np.testing.assert_array_equal(np.asarray(state.elig), ELIG_MAX)
    # silence decays by exactly one right shift per step
    state = rule.step(state, jnp.zeros((4,)), depth=7)
    np.testing.assert_array_equal(np.asarray(state.elig), ELIG_MAX >> 1)
    state = rule.step(state, jnp.zeros((4,)), depth=7)
    np.testing.assert_array_equal(np.asarray(state.elig), ELIG_MAX >> 2)
    # a lone spike injects the fixed credit on top of the decayed word
    state = rule.step(state, ones, depth=7)
    np.testing.assert_array_equal(
        np.asarray(state.elig), (ELIG_MAX >> 3) + ELIG_INJECT
    )


def test_readout_is_one_extra_register_row():
    rule = get_rule("mstdp")
    state = rule.init_state(6, 5)
    state = rule.step(state, jnp.ones((6,)), depth=5)
    arr = rule.readout(state)
    assert arr.shape == (6, 6)  # depth history rows + 1 eligibility row
    assert arr.dtype == jnp.uint8
    np.testing.assert_array_equal(
        np.asarray(arr[:-1]), np.asarray(H.registers_depth_major(state.hist))
    )
    np.testing.assert_array_equal(np.asarray(arr[-1]), np.asarray(state.elig))
    np.testing.assert_array_equal(
        np.asarray(rule.last_spikes(state)), np.ones(6, np.float32)
    )


# ---------------------------------------------------------------------------
# Every declared backend, for free
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", MSTDP_BACKENDS)
def test_mstdp_runs_on_every_backend(key, backend):
    state0, final, _ = _run(key, backend)
    w = np.asarray(final.w)
    assert np.isfinite(w).all()
    assert (w >= 0.0).all() and (w <= 1.0).all()
    assert not np.array_equal(w, np.asarray(state0.w))
    assert final.pre_hist.elig.dtype == jnp.uint8


@pytest.mark.parametrize("backend", ("fused_interpret", "sparse"))
def test_mstdp_backends_match_reference(key, backend):
    _, ref, post_ref = _run(key, "reference")
    _, got, post_got = _run(key, backend)
    np.testing.assert_allclose(np.asarray(got.w), np.asarray(ref.w),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(post_got), np.asarray(post_ref))


@pytest.mark.parametrize("backend", ("reference", "fused_interpret"))
def test_mstdp_crosses_sharded_engine(key, backend):
    from repro.core.engine_sharded import (make_sharded_engine_step,
                                           shard_engine_state)

    cfg = EngineConfig(n_pre=16, n_post=8, rule="mstdp", backend=backend)
    state0 = init_engine(key, cfg)
    train = jax.random.bernoulli(key, 0.3, (16, cfg.n_pre))
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


# ---------------------------------------------------------------------------
# Reward semantics
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("reward", [0.0], indirect=True)
def test_zero_reward_freezes_learning(key, reward):
    state0, final, _ = _run(key, "reference")
    np.testing.assert_array_equal(np.asarray(final.w), np.asarray(state0.w))


@pytest.mark.parametrize("reward", [-1.0], indirect=True)
def test_negative_reward_flips_update_direction(key, reward):
    state0, neg, _ = _run(key, "reference")
    RULES["mstdp"] = MSTDP  # reward=+1 for the comparison run
    _, pos, _ = _run(key, "reference")
    RULES["mstdp"] = MSTDPRule(reward=-1.0)  # fixture teardown expects it
    dw_pos = np.asarray(pos.w) - np.asarray(state0.w)
    dw_neg = np.asarray(neg.w) - np.asarray(state0.w)
    moved = dw_pos != 0.0
    assert moved.any()
    # away from the clip rails the negated reward negates the trajectory's
    # first-step delta; over a scan the paths diverge, so pin directions
    assert (np.sign(dw_neg[moved]) != np.sign(dw_pos[moved])).mean() > 0.5


@pytest.mark.parametrize("reward", [0.5], indirect=True)
def test_reward_is_static_replace_field(key, reward):
    assert get_rule("mstdp").reward == 0.5
    assert dataclasses.replace(MSTDP, reward=0.25).reward == 0.25


# ---------------------------------------------------------------------------
# Through the trainer (network level)
# ---------------------------------------------------------------------------


def test_mstdp_through_stdp_trainer():
    from repro.launch import cli
    from repro.models import snn
    from repro.train.stdp_trainer import TrainerConfig, train_to_accuracy

    sampler, n_classes = cli.sampler_for("2layer-snn")
    cfg = snn.mnist_2layer("mstdp", n_hidden=16, backend="fused_interpret",
                           theta_plus=0.05, hard_wta=True)
    tcfg = TrainerConfig(epochs=1, batches_per_epoch=2, batch=4, t_steps=10,
                         assign_batches=2, eval_batches=2)
    r = train_to_accuracy(cfg, sampler, n_classes, tcfg)
    assert len(r["accuracy_curve"]) == 1
    assert np.isfinite(r["final_accuracy"])
    assert r["sim_steps"] == 2 * 10
