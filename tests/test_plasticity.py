"""Unified LearningRule API: registry, rule × backend matrix and trajectory
pins.

The load-bearing contracts:

  * ``rule="itp"`` through the new API is bit-identical to the
    pre-redesign engine datapath (the manual loop below replicates the
    old ``engine_step`` ops exactly — array_equal, not allclose);
  * ``rule="exact"`` (the counter-based baseline folded into the rule
    registry) reproduces compensated ITP trajectories — the paper's
    eq. 18 equivalence at the system level;
  * invalid rule/backend names and kernel-less rule + fused* cells fail
    at config-construction time with the valid options listed.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import plasticity
from repro.core import history as H
from repro.core.engine import EngineConfig, init_engine, run_engine
from repro.core.lif import lif_step
from repro.core.stdp import magnitudes_depth_major, pair_gate
from repro.models import snn

T_STEPS = 40


# ---------------------------------------------------------------------------
# Registry + error paths (config-construction time)
# ---------------------------------------------------------------------------

def test_registry_contents():
    names = plasticity.rule_names()
    assert set(names) >= {"itp", "itp_nocomp", "exact", "linear", "imstdp",
                          "mstdp"}
    # every registered rule is kernel-backed since the itp_counter package
    # closed the counter side of the rule × backend matrix (PR 5)
    assert set(plasticity.kernel_rule_names()) == set(names)
    assert plasticity.get_rule("itp").has_kernel
    assert plasticity.get_rule("exact").has_kernel


def test_unknown_rule_lists_options():
    with pytest.raises(ValueError, match="unknown learning rule.*itp"):
        EngineConfig(rule="hebbian")
    with pytest.raises(ValueError, match="unknown learning rule.*itp"):
        snn.mnist_2layer("hebbian", n_hidden=8)


def test_unknown_backend_lists_options():
    with pytest.raises(ValueError, match="unknown backend.*reference"):
        EngineConfig(backend="cuda")
    with pytest.raises(ValueError, match="unknown backend.*reference"):
        snn.mnist_2layer("itp", n_hidden=8, backend="cuda")


@pytest.mark.parametrize("backend", ["fused", "fused_interpret"])
@pytest.mark.parametrize("rule", ["exact", "linear", "imstdp"])
def test_counter_rule_fused_cells_construct(rule, backend):
    """The former ValueError cells of the rule × backend matrix are open:
    counter rules are kernel-backed (repro.kernels.itp_counter)."""
    assert EngineConfig(rule=rule, backend=backend).backend == backend
    assert snn.mnist_2layer(rule, n_hidden=8, backend=backend).rule == rule


def test_kernel_less_rule_rejects_fused():
    """A rule without a kernel still fails fast on the fused* backends with
    the actionable alternatives (the config-construction-time contract)."""
    class NoKernelRule(plasticity.CounterRule):
        pass

    rule = NoKernelRule(name="nokernel", has_kernel=False)
    with pytest.raises(ValueError, match="no fused kernel.*reference"):
        plasticity.resolve_rule_backend(rule, "fused_interpret")
    assert plasticity.resolve_rule_backend(rule, "reference") == (False, False)


def test_counter_rule_rejects_all_to_all():
    with pytest.raises(ValueError, match="nearest"):
        EngineConfig(rule="exact", pairing="all")


def test_sparse_rule_registry():
    """Only rules with event hooks open the sparse backend column: the
    history family plus the Rank1Rule-derived mstdp."""
    assert set(plasticity.sparse_rule_names()) == {"itp", "itp_nocomp", "mstdp"}
    assert plasticity.get_rule("itp").has_sparse
    assert not plasticity.get_rule("exact").has_sparse
    # sparse maps to the non-Pallas path: consumers branch explicitly
    rule = plasticity.get_rule("itp")
    assert plasticity.resolve_rule_backend(rule, "sparse") == (False, False)


@pytest.mark.parametrize("rule", ["exact", "linear", "imstdp"])
def test_counter_rule_rejects_sparse_at_construction(rule):
    """A rule without event hooks fails at config construction — never at
    trace time — and the message lists the valid alternatives."""
    with pytest.raises(ValueError, match="event-driven.*itp.*reference"):
        EngineConfig(rule=rule, backend="sparse")
    with pytest.raises(ValueError, match="event-driven.*itp.*reference"):
        snn.mnist_2layer(rule, n_hidden=8, backend="sparse")
    with pytest.raises(ValueError, match="event-driven"):
        plasticity.resolve_rule_backend(plasticity.get_rule(rule), "sparse")


def test_sparse_cells_construct_for_history_rules():
    for rule in plasticity.sparse_rule_names():
        assert EngineConfig(rule=rule, backend="sparse").backend == "sparse"
        assert snn.mnist_2layer(rule, n_hidden=8,
                                backend="sparse").backend == "sparse"


def test_launcher_cli_rejects_bad_rule():
    """argparse surfaces the registry as --rule choices."""
    import subprocess
    import sys
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--engine",
         "--rule", "hebbian"],
        capture_output=True, text=True,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin"})
    assert r.returncode != 0
    assert "--rule" in r.stderr and "itp" in r.stderr


def test_history_rule_last_spikes_reads_newest_bit_without_relayout(key):
    """The hot-path newest-spike readout is planes[head] directly and must
    equal the k=0 column of the full (N, depth) register materialisation —
    for every ring-buffer head position, including pre-wrap and post-wrap."""
    rule = plasticity.get_rule("itp")
    n, depth = 13, 7
    state = rule.init_state(n, depth)
    np.testing.assert_array_equal(np.asarray(rule.last_spikes(state)),
                                  np.zeros(n, np.float32))
    for t in range(2 * depth + 3):                # wraps the ring twice
        spikes = jax.random.bernoulli(jax.random.fold_in(key, t), 0.4, (n,))
        state = rule.step(state, spikes, depth=depth)
        want = np.asarray(H.as_register(state))[:, 0].astype(np.float32)
        got = np.asarray(rule.last_spikes(state))
        np.testing.assert_array_equal(got, want)
        np.testing.assert_array_equal(got, np.asarray(spikes, np.float32))


def test_history_rule_packed_readout_matches_pack_words(key):
    """readout_packed is the registry view of pack_words for the history
    rules; for the counter rules it is the saturating counter itself as a
    uint8 word — the same shape/sharding contract either way."""
    rule = plasticity.get_rule("itp")
    state = rule.init_state(9, 7)
    for t in range(5):
        state = rule.step(state, jax.random.bernoulli(
            jax.random.fold_in(key, t), 0.5, (9,)), depth=7)
    np.testing.assert_array_equal(np.asarray(rule.readout_packed(state)),
                                  np.asarray(H.pack_words(state)))
    exact = plasticity.get_rule("exact")
    cstate = exact.init_state(4, 7)
    cstate = exact.step(cstate, jnp.array([1, 0, 0, 1]), depth=7)
    words = exact.readout_packed(cstate)
    assert words.dtype == jnp.uint8 and words.shape == (4,)
    np.testing.assert_array_equal(np.asarray(words),
                                  np.asarray(cstate, np.uint8))


# ---------------------------------------------------------------------------
# Trajectory pins
# ---------------------------------------------------------------------------

def _pre_redesign_reference_step(state, pre_spikes, cfg):
    """The pre-redesign engine_step ops, verbatim (reference backend)."""
    pre_spikes = jnp.asarray(pre_spikes)
    i_in = pre_spikes.astype(jnp.float32) @ state.w
    neurons, post_spikes = lif_step(state.neurons, i_in, cfg.lif)
    ltp_mag = magnitudes_depth_major(
        H.registers_depth_major(state.pre_hist), cfg.stdp.a_plus,
        cfg.stdp.tau_plus, pairing=cfg.pairing, compensate=cfg.compensate)
    ltd_mag = magnitudes_depth_major(
        H.registers_depth_major(state.post_hist), cfg.stdp.a_minus,
        cfg.stdp.tau_minus, pairing=cfg.pairing, compensate=cfg.compensate)
    ltp_en, ltd_en = pair_gate(pre_spikes[:, None], post_spikes[None, :])
    dw = ltp_en * ltp_mag[:, None] - ltd_en * ltd_mag[None, :]
    w = jnp.clip(state.w + cfg.eta * dw, cfg.w_min, cfg.w_max)
    pre_hist = H.push(state.pre_hist, pre_spikes)
    post_hist = H.push(state.post_hist, post_spikes)
    return type(state)(w, pre_hist, post_hist, neurons), post_spikes


@pytest.mark.parametrize("pairing", ["nearest", "all"])
def test_itp_through_rule_api_bit_identical_to_pre_redesign(key, pairing):
    cfg = EngineConfig(n_pre=24, n_post=16, eta=0.25, pairing=pairing)
    state = init_engine(key, cfg)
    train = jax.random.bernoulli(key, 0.35, (T_STEPS, cfg.n_pre))
    s_new, post_new = run_engine(state, train, cfg)
    s_old = state
    posts = []
    for t in range(T_STEPS):
        s_old, post = _pre_redesign_reference_step(s_old, train[t], cfg)
        posts.append(np.asarray(post))
    np.testing.assert_array_equal(np.asarray(s_new.w), np.asarray(s_old.w))
    np.testing.assert_array_equal(np.asarray(post_new), np.stack(posts))


def test_exact_rule_matches_compensated_itp_engine(key):
    """eq. 18 at the system level: the counter-based exact baseline and the
    intrinsic-timing compensated po2 rule produce the same trajectory."""
    kw = dict(n_pre=20, n_post=12, eta=0.25)
    cfg_itp = EngineConfig(rule="itp", **kw)
    cfg_exact = EngineConfig(rule="exact", **kw)
    train = jax.random.bernoulli(key, 0.35, (T_STEPS, kw["n_pre"]))
    s_itp, post_itp = run_engine(init_engine(key, cfg_itp), train, cfg_itp)
    s_ex, post_ex = run_engine(init_engine(key, cfg_exact), train, cfg_exact)
    np.testing.assert_allclose(np.asarray(s_ex.w), np.asarray(s_itp.w),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(post_ex), np.asarray(post_itp))


@pytest.mark.parametrize("rule", ["linear", "imstdp"])
def test_baseline_rules_run_and_stay_bounded(key, rule):
    cfg = EngineConfig(n_pre=16, n_post=8, rule=rule, eta=0.5)
    train = jax.random.bernoulli(key, 0.4, (T_STEPS, 16))
    s, post = run_engine(init_engine(key, cfg), train, cfg)
    assert post.shape == (T_STEPS, 8)
    w = np.asarray(s.w)
    assert not np.isnan(w).any()
    assert w.min() >= cfg.w_min and w.max() <= cfg.w_max


def test_linear_rule_differs_from_exact(key):
    # small eta + short run so neither rule saturates at w_max
    kw = dict(n_pre=16, n_post=8, eta=1.0 / 64.0, quantise=False)
    train = jax.random.bernoulli(key, 0.4, (10, 16))
    ws = {}
    for rule in ("exact", "linear"):
        cfg = EngineConfig(rule=rule, **kw)
        s, _ = run_engine(init_engine(key, cfg), train, cfg)
        ws[rule] = np.asarray(s.w)
    assert np.abs(ws["exact"] - ws["linear"]).max() > 1e-4


# ---------------------------------------------------------------------------
# Network-level rule dispatch
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("net", ["2layer-snn", "5layer-csnn"])
def test_snn_exact_counter_rule_matches_itp(key, net):
    """Table II mechanism through the new API: the counter-based 'exact'
    rule and compensated 'itp' give the same run — for the fc network
    (reference einsum path) and a conv network (the counter-rule patch
    path vs the history-rule im2col oracle)."""
    B, T = 4, 12
    makers = {
        "2layer-snn": lambda r: snn.mnist_2layer(r, n_hidden=20,
                                                 quantise=False),
        "5layer-csnn": lambda r: snn.fault_csnn(r, quantise=False),
    }
    n_in = {"2layer-snn": 28 * 28, "5layer-csnn": 512 * 2}[net]
    raster = jax.random.bernoulli(key, 0.2, (T, B, n_in))
    outs = {}
    for rule in ("exact", "itp"):
        cfg = makers[net](rule)
        st = snn.init_snn(jax.random.PRNGKey(7), cfg, B)
        st2, counts = snn.run_snn(st, raster, cfg, train=True)
        outs[rule] = ([np.asarray(w) for w in st2.weights],
                      np.asarray(counts))
    for w_ex, w_itp in zip(outs["exact"][0], outs["itp"][0]):
        np.testing.assert_allclose(w_ex, w_itp, rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(outs["exact"][1], outs["itp"][1])


@pytest.mark.parametrize("rule", ["linear", "imstdp"])
def test_snn_counter_rules_learn_on_conv_net(key, rule):
    """Counter rules drive the conv nets through the reference patch path."""
    cfg = snn.fault_csnn(rule)
    B, T = 2, 8
    st = snn.init_snn(key, cfg, B)
    raster = jax.random.bernoulli(key, 0.3, (T, B, 512 * 2))
    st2, counts = snn.run_snn(st, raster, cfg, train=True)
    assert not np.isnan(np.asarray(counts)).any()
    moved = sum(float(jnp.abs(w2 - w1).max())
                for w1, w2 in zip(st.weights, st2.weights))
    assert moved > 1e-6
    for w in st2.weights:
        assert float(w.min()) >= 0.0 and float(w.max()) <= 1.0


def test_launcher_engine_mode_runs_counter_rule():
    """--engine --rule exact --backend reference end-to-end."""
    import argparse

    from repro.launch.train import run_engine_training

    args = argparse.Namespace(rule="exact", backend="reference",
                              engine_pre=16, engine_post=16, replicas=2,
                              steps=8, engine_rate=0.3)
    summary = run_engine_training(args)
    assert summary["rule"] == "exact"
    assert summary["sops_per_s"] > 0
