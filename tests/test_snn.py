"""Paper networks (§IV-C): structure, learning, and the Table II parity
protocol at smoke scale."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data import encode_batch, synthetic_digits, synthetic_fault
from repro.models import snn


@pytest.mark.slow
@pytest.mark.parametrize("maker,sampler", [
    (snn.mnist_2layer, lambda k, n: synthetic_digits(k, n)),
    (snn.fmnist_dcsnn, lambda k, n: synthetic_digits(k, n)),
    (snn.fault_csnn, lambda k, n: synthetic_fault(k, n, length=512)),
])
def test_network_step_shapes(key, maker, sampler):
    cfg = maker("itp")
    B, T = 2, 8
    st = snn.init_snn(key, cfg, B)
    x, y = sampler(key, B)
    raster = encode_batch(key, x, T)
    st2, counts = snn.run_snn(st, raster, cfg, train=True)
    assert counts.shape == (B, snn.feature_size(cfg))
    assert not np.isnan(np.asarray(counts)).any()
    for w in st2.weights:
        assert float(w.min()) >= 0.0 and float(w.max()) <= 1.0


def test_weights_learn(key):
    cfg = snn.mnist_2layer("itp", quantise=False)
    B, T = 8, 20
    st = snn.init_snn(key, cfg, B)
    x, _ = synthetic_digits(key, B)
    raster = encode_batch(key, x, T)
    st2, _ = snn.run_snn(st, raster, cfg, train=True)
    assert float(jnp.abs(st2.weights[0] - st.weights[0]).max()) > 1e-4


def test_train_false_freezes_weights(key):
    cfg = snn.fault_csnn("itp")
    B, T = 2, 10
    st = snn.init_snn(key, cfg, B)
    x, _ = synthetic_fault(key, B, length=512)
    raster = encode_batch(key, x, T)
    st2, _ = snn.run_snn(st, raster, cfg, train=False)
    for w1, w2 in zip(st.weights, st2.weights):
        np.testing.assert_array_equal(np.asarray(w1), np.asarray(w2))


def test_exact_and_compensated_itp_identical_trajectories(key):
    """Table II mechanism: 'exact' and compensated ITP read the same
    e^(-k/τ) values on the integer grid — identical runs, not just
    statistically similar."""
    B, T = 4, 15
    x, _ = synthetic_digits(key, B)
    raster = encode_batch(key, x, T)
    outs = {}
    for rule in ("exact", "itp"):
        cfg = snn.mnist_2layer(rule, quantise=False)
        st = snn.init_snn(jax.random.PRNGKey(7), cfg, B)
        st2, counts = snn.run_snn(st, raster, cfg, train=True)
        outs[rule] = (np.asarray(st2.weights[0]), np.asarray(counts))
    np.testing.assert_allclose(outs["exact"][0], outs["itp"][0], rtol=1e-6)
    np.testing.assert_array_equal(outs["exact"][1], outs["itp"][1])


def test_uncompensated_differs_but_close(key):
    B, T = 4, 15
    x, _ = synthetic_digits(key, B)
    raster = encode_batch(key, x, T)
    w = {}
    for rule in ("itp", "itp_nocomp"):
        cfg = snn.mnist_2layer(rule, quantise=False)
        st = snn.init_snn(jax.random.PRNGKey(7), cfg, B)
        st2, _ = snn.run_snn(st, raster, cfg, train=True)
        w[rule] = np.asarray(st2.weights[0])
    diff = np.abs(w["itp"] - w["itp_nocomp"])
    assert diff.max() > 1e-6          # the rules do differ...
    assert diff.max() < 0.2           # ...by a bounded amount (§IV-A)


def test_quantised_weights_on_grid(key):
    cfg = snn.mnist_2layer("itp", quantise=True, w_bits=8)
    B, T = 4, 10
    st = snn.init_snn(key, cfg, B)
    x, _ = synthetic_digits(key, B)
    st2, _ = snn.run_snn(st, encode_batch(key, x, T), cfg, train=True)
    levels = (1 << (cfg.w_bits - 1)) - 1
    scaled = np.asarray(st2.weights[0]) * levels
    np.testing.assert_allclose(scaled, np.round(scaled), atol=1e-4)


def _assert_same_leaves(a, b):
    """Equal leaf for leaf: tree structure, dtypes, shapes, values."""
    la, ta = jax.tree.flatten(a)
    lb, tb = jax.tree.flatten(b)
    assert ta == tb
    for x, y in zip(la, lb):
        assert x.dtype == y.dtype and x.shape == y.shape
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("net,rule", [
    ("2layer-snn", "itp"), ("6layer-dcsnn", "itp"), ("2layer-snn", "exact")])
def test_reset_dynamics_equals_a_fresh_init(key, net, rule):
    """The reset's layers are a fresh ``init_snn``'s, leaf for leaf, but
    for θ; weights and θ are the state's own, also after a ``run_snn``
    between two resets."""
    cfg = snn.PAPER_NETWORKS[net](rule, theta_plus=0.05)
    B = 2
    fresh = snn.init_snn(jax.random.PRNGKey(9), cfg, B).layers
    if rule == "exact":   # counters start saturated: no spike in the window
        np.testing.assert_array_equal(np.asarray(fresh[0].pre_hist), cfg.depth)
    st = snn.init_snn(key, cfg, B)
    x, _ = synthetic_digits(key, B)
    st1 = snn.reset_dynamics(st, cfg, B)
    st2, _ = snn.run_snn(st1, encode_batch(key, x, 4), cfg, train=True)
    st3 = snn.reset_dynamics(st2, cfg, B)
    assert float(jnp.abs(st2.layers[0].theta).max()) > 0.0
    for before, after in ((st, st1), (st2, st3)):
        assert all(w is v for w, v in zip(after.weights, before.weights))
        kept = tuple(f._replace(theta=b.theta) if b.theta is not None else f
                     for f, b in zip(fresh, before.layers))
        _assert_same_leaves(after.layers, kept)


def test_reset_dynamics_after_the_first_moves_nothing(key):
    """Past the first call for a ``(cfg, batch)`` the reset hands back the
    same fresh arrays: no transfer, nothing built on the device."""
    cfg = snn.fmnist_dcsnn("itp")
    B = 2
    st = snn.reset_dynamics(snn.init_snn(key, cfg, B), cfg, B)
    with jax.transfer_guard("disallow"):
        st2 = snn.reset_dynamics(st, cfg, B)
    for a, b in zip(jax.tree.leaves(st2), jax.tree.leaves(st)):
        assert a is b


def test_reset_dynamics_under_jit_caches_no_tracers(key):
    cfg = snn.mnist_2layer("itp", theta_plus=0.05)
    B = 3
    st = snn.init_snn(key, cfg, B)
    snn._reset_layers.cache_clear()
    traced = jax.jit(lambda s: snn.reset_dynamics(s, cfg, B))(st)
    eager = snn.reset_dynamics(st, cfg, B)
    assert snn._reset_layers.cache_info().misses == 1
    assert not any(isinstance(leaf, jax.core.Tracer)
                   for leaf in jax.tree.leaves(snn._reset_layers(cfg, B)))
    _assert_same_leaves(traced, eager)


@pytest.mark.slow
def test_learning_beats_chance(key):
    """End-to-end protocol at tiny scale: STDP features + ridge readout
    beat chance on the synthetic digits."""
    cfg = snn.mnist_2layer("itp")
    B, T, rounds = 16, 25, 4
    st = snn.init_snn(key, cfg, B)
    k = key
    for _ in range(rounds):
        k, kd, ke = jax.random.split(k, 3)
        x, _ = synthetic_digits(kd, B)
        st, _ = snn.run_snn(st, encode_batch(ke, x, T), cfg, train=True)
        st = snn.reset_dynamics(st, cfg, B)

    def feats(n, seed):
        fs, ls = [], []
        kk = jax.random.PRNGKey(seed)
        s = st
        for _ in range(n // B):
            kk, kd, ke = jax.random.split(kk, 3)
            x, y = synthetic_digits(kd, B)
            s = snn.reset_dynamics(s, cfg, B)
            s, c = snn.run_snn(s, encode_batch(ke, x, T), cfg, train=False)
            fs.append(c)
            ls.append(y)
        return jnp.concatenate(fs), jnp.concatenate(ls)

    Xtr, ytr = feats(64, 10)
    Xte, yte = feats(48, 20)
    W = snn.fit_readout(Xtr, ytr, 10)
    acc = snn.readout_accuracy(W, Xte, yte)
    assert acc > 0.15   # chance = 0.10


def test_readout_ridge_sanity(key):
    X = jax.random.normal(key, (200, 16))
    w_true = jax.random.normal(jax.random.fold_in(key, 1), (16, 4))
    y = jnp.argmax(X @ w_true, axis=-1)
    W = snn.fit_readout(X, y, 4, l2=1e-4)
    assert snn.readout_accuracy(W, X, y) > 0.9
