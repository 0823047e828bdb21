"""Train-to-accuracy subsystem: homeostasis + WTA competition, the
label-assignment evaluator, the epoch loop, the shared CLI builders, and
the EngineConfig/SNNConfig validator parity pin."""

import argparse

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import EngineConfig
from repro.data import encode_batch, synthetic_digits
from repro.launch import cli
from repro.models import snn
from repro.train.stdp_trainer import (
    TrainerConfig,
    assign_labels,
    assignment_accuracy,
    assignment_predict,
    train_to_accuracy,
)


# ---------------------------------------------------------------------------
# Homeostasis + hard WTA (network-level competition dynamics)
# ---------------------------------------------------------------------------


def _digit_spikes(key, batch, t_steps):
    k_data, k_enc = jax.random.split(key)
    x, _ = synthetic_digits(k_data, batch)
    return encode_batch(k_enc, x, t_steps)


def test_homeostasis_raises_thresholds_of_active_neurons(key):
    cfg = snn.mnist_2layer("itp", n_hidden=32, theta_plus=0.1, theta_tau=50.0)
    state = snn.init_snn(key, cfg, 8)
    spikes = _digit_spikes(key, 8, 20)
    state, counts = snn.run_snn(state, spikes, cfg, train=True)
    theta = np.asarray(state.layers[0].theta)
    totals = np.asarray(counts).sum(axis=0)
    assert theta.shape == (32,)
    assert theta.max() > 0.0, "no threshold moved despite spiking"
    # a neuron that never fired accrues no homeostatic penalty …
    np.testing.assert_allclose(theta[totals == 0.0], 0.0)
    # … and the most active neuron carries a strictly positive one
    assert theta[totals.argmax()] > 0.0


def test_homeostasis_frozen_in_eval_and_survives_reset(key):
    cfg = snn.mnist_2layer("itp", n_hidden=32, theta_plus=0.1, theta_tau=50.0)
    state = snn.init_snn(key, cfg, 8)
    spikes = _digit_spikes(key, 8, 20)
    state, _ = snn.run_snn(state, spikes, cfg, train=True)
    theta = np.asarray(state.layers[0].theta)
    # θ is the slow homeostatic variable: reset_dynamics clears membranes
    # and histories but must carry θ across sample boundaries …
    state = snn.reset_dynamics(state, cfg, 8)
    np.testing.assert_array_equal(np.asarray(state.layers[0].theta), theta)
    # … and a frozen (train=False) pass must not move it
    state, _ = snn.run_snn(state, spikes, cfg, train=False)
    np.testing.assert_array_equal(np.asarray(state.layers[0].theta), theta)


def test_homeostasis_disabled_keeps_theta_zero(key):
    cfg = snn.mnist_2layer("itp", n_hidden=32)
    assert cfg.theta_plus == 0.0
    state = snn.init_snn(key, cfg, 4)
    state, _ = snn.run_snn(state, _digit_spikes(key, 4, 15), cfg, train=True)
    np.testing.assert_allclose(np.asarray(state.layers[0].theta), 0.0)


def test_hard_wta_caps_spikes_per_sample_per_step(key):
    wta = snn.mnist_2layer("itp", n_hidden=32, hard_wta=True)
    soft = snn.mnist_2layer("itp", n_hidden=32)
    spikes = _digit_spikes(key, 8, 25)
    st_wta = snn.init_snn(key, wta, 8)
    st_soft = snn.init_snn(key, soft, 8)
    _, counts_wta = snn.run_snn(st_wta, spikes, wta, train=False)
    _, counts_soft = snn.run_snn(st_soft, spikes, soft, train=False)
    # at most one winner per sample and step → per-sample total ≤ t_steps
    per_sample = np.asarray(counts_wta).sum(axis=1)
    assert per_sample.max() <= 25
    # WTA is strictly a restriction of the soft-inhibition dynamics
    assert np.asarray(counts_wta).sum() <= np.asarray(counts_soft).sum()


# ---------------------------------------------------------------------------
# Label-assignment evaluator
# ---------------------------------------------------------------------------


def test_assign_labels_recovers_class_selective_neurons():
    labels = jnp.array([0, 1, 2, 0, 1, 2])
    # counts[n, f] = 5 if sample n's label == neuron f's preferred class
    counts = 5.0 * (labels[:, None] == (jnp.arange(6)[None, :] % 3))
    assignments = assign_labels(counts, labels, 3)
    np.testing.assert_array_equal(np.asarray(assignments), np.arange(6) % 3)
    pred = assignment_predict(counts, assignments, 3)
    np.testing.assert_array_equal(np.asarray(pred), np.asarray(labels))
    assert assignment_accuracy(counts, labels, assignments, 3) == 1.0


def test_assignment_vote_is_population_mean_not_sum():
    # class 0 owns 3 neurons, class 1 owns 1; a sample driving the class-1
    # neuron harder must win despite class 0's larger population
    assignments = jnp.array([0, 0, 0, 1], jnp.int32)
    counts = jnp.array([[1.0, 1.0, 1.0, 4.0]])
    pred = assignment_predict(counts, assignments, 2)
    assert int(pred[0]) == 1


def test_silent_neurons_carry_no_vote():
    labels = jnp.array([0, 1])
    counts = jnp.array([[3.0, 0.0], [0.0, 0.0]])  # neuron 1 never fires
    assignments = assign_labels(counts, labels, 2)
    assert int(assignments[0]) == 0
    # neuron 1 falls to class 0 by argmax-of-zeros; its zero counts add
    # nothing to either class's mean vote for a firing sample
    pred = assignment_predict(jnp.array([[5.0, 0.0]]), assignments, 2)
    assert int(pred[0]) == 0


# ---------------------------------------------------------------------------
# The epoch loop end-to-end
# ---------------------------------------------------------------------------


def test_train_to_accuracy_beats_chance():
    sampler, n_classes = cli.sampler_for("2layer-snn")
    cfg = snn.mnist_2layer("itp", theta_plus=0.05, hard_wta=True)
    tcfg = TrainerConfig(
        epochs=1,
        batches_per_epoch=6,
        batch=16,
        t_steps=30,
        assign_batches=4,
        eval_batches=4,
    )
    r = train_to_accuracy(cfg, sampler, n_classes, tcfg)
    assert len(r["accuracy_curve"]) == tcfg.epochs
    assert r["final_accuracy"] == r["accuracy_curve"][-1]
    assert r["chance"] == pytest.approx(0.1)
    assert r["final_accuracy"] >= 2 * r["chance"], r["accuracy_curve"]
    assert r["sim_steps"] == 6 * 30
    assert isinstance(r["state"], snn.SNNState)


def test_trainer_config_validates_counts():
    with pytest.raises(ValueError, match="epochs"):
        TrainerConfig(epochs=0)
    with pytest.raises(ValueError, match="eval_batches"):
        TrainerConfig(eval_batches=0)


# ---------------------------------------------------------------------------
# Shared CLI builders (examples/train_snn.py ≡ repro.launch.train --snn)
# ---------------------------------------------------------------------------


def _example_parser():
    ap = argparse.ArgumentParser()
    cli.add_net_flag(ap, "--net")
    cli.add_update_flags(ap)
    cli.add_train_flags(ap)
    return ap


def _launcher_parser():
    ap = argparse.ArgumentParser()
    cli.add_net_flag(ap, "--snn", default=None)
    cli.add_update_flags(ap)
    cli.add_train_flags(ap, batch_default=8)
    return ap


def test_both_entry_points_build_identical_configs():
    flags = "--rule exact --epochs 2 --batch 4 --theta-plus 0.1 --hard-wta"
    argv = ["2layer-snn"] + flags.split() + ["--hidden", "32"]
    a = _example_parser().parse_args(["--net"] + argv)
    b = _launcher_parser().parse_args(["--snn"] + argv)
    assert cli.net_from_args(a) == cli.net_from_args(b) == "2layer-snn"
    assert cli.snn_config_from_args(a) == cli.snn_config_from_args(b)
    assert cli.trainer_config_from_args(a) == cli.trainer_config_from_args(b)
    cfg = cli.snn_config_from_args(a)
    assert cfg.rule == "exact" and cfg.hard_wta and cfg.theta_plus == 0.1
    tcfg = cli.trainer_config_from_args(a)
    assert tcfg.epochs == 2 and tcfg.batch == 4


def test_unset_flags_defer_to_maker_defaults():
    args = _example_parser().parse_args(["--net", "2layer-snn"])
    cfg = cli.snn_config_from_args(args)
    # mnist_2layer's own soft inhibition survives when --inhibition unset
    assert cfg == snn.mnist_2layer("itp")
    assert cli.trainer_config_from_args(args) == TrainerConfig()


def test_legacy_steps_namespace_maps_to_one_epoch():
    args = argparse.Namespace(snn="2layer-snn", batch=8, steps=60, engine_rate=0.3)
    assert cli.net_from_args(args) == "2layer-snn"
    tcfg = cli.trainer_config_from_args(args)
    assert tcfg.epochs == 1
    assert tcfg.t_steps == 30 and tcfg.batches_per_epoch == 2
    assert tcfg.batch == 8
    cfg = cli.snn_config_from_args(args)
    assert cfg.rule == "itp" and cfg.backend == "reference"


def test_samplers_cover_every_paper_network():
    assert set(cli.SAMPLERS) == set(snn.PAPER_NETWORKS)
    for net in cli.SAMPLERS:
        sampler, n_classes = cli.sampler_for(net)
        x, y = sampler(jax.random.PRNGKey(0), 3)
        assert x.shape[0] == 3 and y.shape == (3,)
        assert n_classes >= 2


def test_launcher_snn_mode_reports_accuracy():
    from repro.launch.train import run_snn_training

    args = argparse.Namespace(
        net="2layer-snn",
        rule="itp",
        backend="reference",
        hidden=32,
        epochs=1,
        batches_per_epoch=2,
        batch=4,
        t_raster=10,
        assign_batches=2,
        eval_batches=2,
        theta_plus=0.05,
        hard_wta=True,
    )
    summary = run_snn_training(args)
    assert summary["net"] == "2layer-snn"
    assert summary["epochs"] == 1 and summary["steps"] == 2 * 10
    assert summary["sops_per_s"] > 0
    assert len(summary["accuracy_curve"]) == 1
    assert 0.0 <= summary["final_accuracy"] <= 1.0


def test_launcher_without_a_mode_is_a_usage_error(monkeypatch, capsys):
    """With neither --snn nor --engine the launcher picks no mode on its own."""
    from repro.launch import train

    monkeypatch.setattr("sys.argv", ["train.py", "--steps", "4"])
    with pytest.raises(SystemExit) as exc:
        train.main()
    assert exc.value.code == 2
    err = capsys.readouterr().err
    assert "--snn" in err and "--engine" in err


# ---------------------------------------------------------------------------
# Validator parity: EngineConfig and SNNConfig share one message surface
# ---------------------------------------------------------------------------


def _raises_message(fn):
    with pytest.raises(ValueError) as exc:
        fn()
    return str(exc.value)


@pytest.mark.parametrize(
    "kw",
    [
        {"rule": "hebbian"},
        {"rule": "exact", "backend": "sparse"},
        {"backend": "sparse", "max_events": 0},
        {"pairing": "both"},
    ],
)
def test_engine_and_snn_configs_raise_identical_messages(kw):
    rule = kw.pop("rule", "itp")
    m_engine = _raises_message(lambda: EngineConfig(rule=rule, **kw))
    m_snn = _raises_message(lambda: snn.mnist_2layer(rule, **kw))
    assert m_engine == m_snn
