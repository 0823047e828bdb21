"""The plain reference: its 1-D layers against the program, and its
outputs on the accepted configurations pinned to recorded values.

On the CPU both sides run in f32: the program under
``default_matmul_precision("highest")`` (its update kernels ask for
HIGHEST themselves), the reference with forward and update at
``"highest"``.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import compare
import harness
from configs import snn_reference as ref
from repro.models import snn

DATA = harness.BENCH / "tests" / "data"
PINNED_SEED = 20260601


def raster_outputs(ref_mod, c: dict, seed: int, batch: int = 2, t_steps: int = 30) -> dict:
    """The reference's outputs for one training and one inference raster
    of configuration ``c`` from weights and input drawn from ``seed``:
    last-layer counts, per learnable layer the trained weights as levels
    of the 8-bit grid, the registers as packed bits and the spike counts."""
    rc = ref_mod.RefConfig.from_file(c)
    k_w, k_x = jax.random.split(jax.random.PRNGKey(seed))
    w0 = ref_mod.init_weights(k_w, rc, 0.2, 0.8)
    n_in = math.prod(rc.input_shape)
    raster = (jax.random.uniform(k_x, (t_steps, batch, n_in)) < 0.3).astype(jnp.uint8)
    levels = (1 << (rc.w_bits - 1)) - 1
    out = {}
    for train in (True, False):
        w, counts, layers = ref_mod.run_raster(rc, w0, raster, train=train)
        tag = "train" if train else "infer"
        out[f"{tag}.counts"] = np.asarray(counts).astype(np.uint8)
        for i, (wi, l) in enumerate(zip(w, layers)):
            if train:
                out[f"{tag}.w{i}"] = np.round(np.asarray(wi) * levels).astype(np.uint8)
            for side in ("pre", "post"):
                out[f"{tag}.{side}{i}"] = np.packbits(np.asarray(l[side]).reshape(-1))
            out[f"{tag}.spikes{i}"] = np.asarray(l["spikes"]).astype(np.uint8)
    return out


@pytest.mark.parametrize("name", ["2layer-snn", "6layer-dcsnn"])
def test_accepted_configs_give_the_recorded_outputs(name):
    """``run_raster`` of both accepted configurations, as it was before the
    reference took 1-D layers (``reference_<name>.npz``).  Tolerance 0: the
    2-D path is the same code on the same platform.  The trained weights
    are quantised, so their grid levels are their values."""
    c = harness.load_json(harness.BENCH / "configs" / f"{name}.json")
    got = raster_outputs(ref, c, PINNED_SEED)
    want = dict(np.load(DATA / f"reference_{name}.npz"))
    assert sorted(got) == sorted(want)
    for key in want:
        np.testing.assert_array_equal(got[key], want[key], err_msg=key)


def test_patches_1d_order_is_the_programs():
    from repro.kernels.dispatch import im2col_1d

    x = jax.random.uniform(jax.random.PRNGKey(3), (2, 29, 3))
    for k, s in ((7, 2), (5, 2), (3, 1), (4, 3)):
        np.testing.assert_array_equal(np.asarray(ref.patches_1d(x, k, s)),
                                      np.asarray(im2col_1d(x, k, s)))


def test_pool_1d_truncates_and_ors():
    x = jnp.zeros((1, 7, 2), jnp.uint8).at[0, 1, 0].set(1).at[0, 6, 1].set(1)
    got = np.asarray(ref._pool("pool1d", x, 2))
    assert got.shape == (1, 3, 2)
    np.testing.assert_array_equal(got[0], [[True, False], [False, False], [False, False]])


def _csnn(length: int) -> dict:
    return dict(harness.load_json(DATA / "5layer-csnn.json"), input_shape=[length, 2])


@pytest.mark.parametrize("backend", ["reference", "fused_interpret"])
def test_conv1d_reference_agrees_with_run_snn(backend):
    """``fault_csnn(length=64)``: one training raster on the program and on
    the reference from the program's initial weights, both in f32.

    Tolerance 0 in counts, register bits and weights: both sides contract
    the same f32 products, and the update's other summation order moves a
    weight only where it crosses half a grid level, which none does here."""
    B, T = 3, 12
    c = _csnn(64)
    cfg = snn.fault_csnn(length=64, backend=backend)
    rc = ref.RefConfig.from_file(c, forward="highest", update="highest")
    key = jax.random.PRNGKey(11)
    state = snn.init_snn(key, cfg, B)
    raster = (jax.random.uniform(jax.random.fold_in(key, 1), (T, B, 128)) < 0.3) \
        .astype(jnp.uint8)
    with jax.default_matmul_precision("highest"):
        new, counts = snn.run_snn(state, raster, cfg, train=True)
    w_ref, c_ref, layers = ref.run_raster(rc, state.weights, raster, train=True)

    assert np.asarray(c_ref).sum() > 0
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(c_ref))
    learn = [ls for ls in new.layers if ls.pre_hist is not None]
    assert len(learn) == len(layers) == 3
    for ls, rl in zip(learn, layers):
        for side in ("pre", "post"):
            mine = compare.program_registers(getattr(ls, f"{side}_hist"))
            np.testing.assert_array_equal(mine, np.asarray(rl[side]).reshape(mine.shape))
    for w0, wp, wr in zip(state.weights, new.weights, w_ref):
        wp, wr = np.asarray(wp), np.asarray(wr)
        assert not np.array_equal(wr, np.asarray(w0))
        np.testing.assert_array_equal(wp, wr)
