"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret=True)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.history import init_history, push
from repro.core.lif import LIFParams, LIFState, lif_init, lif_step
from repro.core.stdp import STDPParams, po2_weights, synapse_update


# ---------------------------------------------------------------------------
# ITP-STDP fused kernel
# ---------------------------------------------------------------------------

def _random_setup(key, n_pre, n_post, depth):
    ks = jax.random.split(key, 5)
    w = jax.random.uniform(ks[0], (n_pre, n_post))
    pre_s = jax.random.bernoulli(ks[1], 0.4, (n_pre,)).astype(jnp.float32)
    post_s = jax.random.bernoulli(ks[2], 0.4, (n_post,)).astype(jnp.float32)
    pre_h = jax.random.bernoulli(ks[3], 0.3, (depth, n_pre)).astype(jnp.float32)
    post_h = jax.random.bernoulli(ks[4], 0.3, (depth, n_post)).astype(jnp.float32)
    return w, pre_s, post_s, pre_h, post_h


@pytest.mark.parametrize("n_pre,n_post", [
    (128, 128),
    pytest.param(256, 128, marks=pytest.mark.slow),
    pytest.param(512, 384, marks=pytest.mark.slow),
])
@pytest.mark.parametrize("nearest", [True, False])
@pytest.mark.parametrize("depth", [7, 8])
def test_itp_stdp_kernel_vs_ref(key, n_pre, n_post, nearest, depth):
    from repro.kernels.itp_stdp.kernel import itp_stdp_update
    from repro.kernels.itp_stdp.ref import itp_stdp_update_ref
    w, pre_s, post_s, pre_h, post_h = _random_setup(key, n_pre, n_post, depth)
    p = STDPParams()
    ltp = p.a_plus * po2_weights(depth, p.tau_plus)
    ltd = p.a_minus * po2_weights(depth, p.tau_minus)
    got = itp_stdp_update(w, pre_s, post_s, pre_h, post_h, ltp, ltd,
                          nearest=nearest, eta=0.25, tile_pre=128,
                          tile_post=128, interpret=True)
    want = itp_stdp_update_ref(w, pre_s, post_s, pre_h, post_h, ltp, ltd,
                               nearest=nearest, eta=0.25)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_engine_weight_update_matches_core(key):
    """Kernel wrapper ≡ repro.core.stdp.synapse_update on ragged sizes."""
    from repro.kernels.itp_stdp.ops import engine_weight_update
    n_pre, n_post, depth = 100, 50, 7
    p = STDPParams()
    w = jax.random.uniform(key, (n_pre, n_post))
    pre_s = jax.random.bernoulli(jax.random.fold_in(key, 1), 0.5, (n_pre,))
    post_s = jax.random.bernoulli(jax.random.fold_in(key, 2), 0.5, (n_post,))
    pre_hist = init_history(n_pre, depth)
    post_hist = init_history(n_post, depth)
    for t in range(10):
        pre_hist = push(pre_hist, jax.random.bernoulli(
            jax.random.fold_in(key, 10 + t), 0.3, (n_pre,)).astype(jnp.uint8))
        post_hist = push(post_hist, jax.random.bernoulli(
            jax.random.fold_in(key, 50 + t), 0.3, (n_post,)).astype(jnp.uint8))
    for pairing in ("nearest", "all"):
        got = engine_weight_update(w, pre_s, post_s, pre_hist, post_hist, p,
                                   pairing=pairing, eta=0.5, use_kernel=True,
                                   interpret=True)
        from repro.core.history import as_register
        want = synapse_update(w, pre_s, post_s, as_register(pre_hist),
                              as_register(post_hist), p, pairing=pairing,
                              eta=0.5)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# Packed uint8 history datapath (the storage format the fused path runs on)
# ---------------------------------------------------------------------------

def _rolled_histories(key, n_pre, n_post, depth, steps=11):
    pre_h = init_history(n_pre, depth)
    post_h = init_history(n_post, depth)
    for t in range(steps):
        pre_h = push(pre_h, jax.random.bernoulli(
            jax.random.fold_in(key, 10 + t), 0.3, (n_pre,)).astype(jnp.uint8))
        post_h = push(post_h, jax.random.bernoulli(
            jax.random.fold_in(key, 50 + t), 0.3, (n_post,)).astype(jnp.uint8))
    return pre_h, post_h


@pytest.mark.parametrize("pairing", ["nearest", "all"])
@pytest.mark.parametrize("depth", [7, 8])
def test_packed_kernel_bit_identical_to_unpacked(key, depth, pairing):
    """The packed-word kernel is *bit-identical* (array_equal, not allclose)
    to the bitplane kernel: the in-register shift+mask unpack reproduces the
    exact operands, and both route through the same fused body."""
    from repro.core.history import pack_words, registers_depth_major
    from repro.kernels.itp_stdp.ops import (weight_update_depth_major,
                                            weight_update_packed)
    n_pre, n_post = 100, 50
    pre_h, post_h = _rolled_histories(key, n_pre, n_post, depth)
    p = STDPParams()
    w = jax.random.uniform(key, (n_pre, n_post))
    pre_s = jax.random.bernoulli(jax.random.fold_in(key, 1), 0.5, (n_pre,))
    post_s = jax.random.bernoulli(jax.random.fold_in(key, 2), 0.5, (n_post,))
    unpacked = weight_update_depth_major(
        w, pre_s, post_s, registers_depth_major(pre_h),
        registers_depth_major(post_h), p, pairing=pairing, eta=0.5,
        interpret=True)
    packed = weight_update_packed(
        w, pre_s, post_s, pack_words(pre_h), pack_words(post_h), p,
        depth=depth, pairing=pairing, eta=0.5, interpret=True)
    np.testing.assert_array_equal(np.asarray(packed), np.asarray(unpacked))
    # the packed reference (unpack + jnp oracle) agrees too
    ref = weight_update_packed(
        w, pre_s, post_s, pack_words(pre_h), pack_words(post_h), p,
        depth=depth, pairing=pairing, eta=0.5, use_kernel=False)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(unpacked),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("depth", [7, 8])
def test_packed_kernel_reads_fixed_point_place_values(key, depth):
    """fixed_point_value is the packed kernel's place-value oracle: with the
    raw po2 read (A=1, τ=1, uncompensated ⇒ read vector 2^-k), all-to-all
    pairing, and only the post side firing, every synapse row i receives
    exactly the binary-fraction value of neuron i's packed word (eq. 2)."""
    from repro.core.history import fixed_point_value, pack_words
    from repro.kernels.itp_stdp.kernel import itp_stdp_update_packed
    from repro.core.stdp import po2_weights
    n = 128
    pre_h, post_h = _rolled_histories(key, n, n, depth)
    words = pack_words(pre_h)
    po2 = po2_weights(depth, 1.0, compensate=False)      # exactly 2^-k
    out = itp_stdp_update_packed(
        jnp.zeros((n, n), jnp.float32),
        jnp.zeros((n,)), jnp.ones((n,)),                 # post fired alone
        words, pack_words(post_h), po2, po2,
        depth=depth, nearest=False, eta=1.0,
        w_min=float("-inf"), w_max=float("inf"),
        tile_pre=128, tile_post=128, interpret=True)
    want = np.asarray(fixed_point_value(words, depth))   # (n,)
    np.testing.assert_allclose(np.asarray(out),
                               np.broadcast_to(want[:, None], (n, n)),
                               rtol=1e-6, atol=1e-6)


def test_engine_weight_update_packed_toggle_matches(key):
    """engine_weight_update(packed=True) ≡ packed=False ≡ core oracle."""
    from repro.core.history import as_register
    from repro.kernels.itp_stdp.ops import engine_weight_update
    n_pre, n_post, depth = 100, 50, 7
    pre_h, post_h = _rolled_histories(key, n_pre, n_post, depth)
    p = STDPParams()
    w = jax.random.uniform(key, (n_pre, n_post))
    pre_s = jax.random.bernoulli(jax.random.fold_in(key, 1), 0.5, (n_pre,))
    post_s = jax.random.bernoulli(jax.random.fold_in(key, 2), 0.5, (n_post,))
    got_packed = engine_weight_update(w, pre_s, post_s, pre_h, post_h, p,
                                      eta=0.5, packed=True, interpret=True)
    got_unpacked = engine_weight_update(w, pre_s, post_s, pre_h, post_h, p,
                                        eta=0.5, packed=False, interpret=True)
    np.testing.assert_array_equal(np.asarray(got_packed),
                                  np.asarray(got_unpacked))
    want = synapse_update(w, pre_s, post_s, as_register(pre_h),
                          as_register(post_h), p, eta=0.5)
    np.testing.assert_allclose(np.asarray(got_packed), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("compensate", [True, False])
@pytest.mark.parametrize("pairing", ["nearest", "all"])
@pytest.mark.parametrize("n_pre,n_post", [(784, 100), (600, 128), (480, 64)])
@pytest.mark.parametrize("batch", [1, 4, 16, 20])
def test_batched_fc_kernels_equal_per_sample_sum(key, batch, n_pre, n_post,
                                                 pairing, compensate):
    """One kernel call contracts the batch: the packed and unpacked kernels
    equal the per-sample sum of the oracle, and each other bit for bit.
    B = 1 comes as 1-D arguments (the engine's call); B = 20 pads the batch
    to sublane rows."""
    from repro.core.history import unpack_words
    from repro.kernels.itp_stdp.ops import (synapse_delta,
                                            synapse_delta_packed,
                                            weight_update_packed)
    from repro.kernels.itp_stdp.ref import itp_stdp_update_ref
    depth = 7
    ks = jax.random.split(key, 5)
    pre_s = jax.random.bernoulli(ks[0], 0.3, (batch, n_pre)).astype(jnp.float32)
    post_s = jax.random.bernoulli(ks[1], 0.3, (batch, n_post)).astype(jnp.float32)
    # depth-7 registers: the low word bit is never set
    pre_w = (jax.random.randint(ks[2], (batch, n_pre), 0, 128) * 2).astype(jnp.uint8)
    post_w = (jax.random.randint(ks[3], (batch, n_post), 0, 128) * 2).astype(jnp.uint8)
    pre_b = jnp.moveaxis(unpack_words(pre_w, depth), -1, 0)     # (depth, B, n)
    post_b = jnp.moveaxis(unpack_words(post_w, depth), -1, 0)
    p = STDPParams()
    ltp = p.a_plus * po2_weights(depth, p.tau_plus, compensate=compensate)
    ltd = p.a_minus * po2_weights(depth, p.tau_minus, compensate=compensate)
    want = sum(
        itp_stdp_update_ref(jnp.zeros((n_pre, n_post)), pre_s[b], post_s[b],
                            pre_b[:, b], post_b[:, b], ltp, ltd,
                            nearest=pairing == "nearest", eta=1.0,
                            w_min=-jnp.inf, w_max=jnp.inf)
        for b in range(batch))
    args = (pre_s, post_s, pre_w, post_w, pre_b, post_b)
    if batch == 1:
        args = tuple(a[..., 0, :] for a in args)
    pre_s, post_s, pre_w, post_w, pre_b, post_b = args
    kw = dict(pairing=pairing, compensate=compensate, interpret=True)
    packed = synapse_delta_packed(pre_s, post_s, pre_w, post_w, p,
                                  depth=depth, **kw)
    unpacked = synapse_delta(pre_s, post_s, pre_b, post_b, p, **kw)
    assert packed.shape == (n_pre, n_post)
    np.testing.assert_array_equal(np.asarray(packed), np.asarray(unpacked))
    np.testing.assert_allclose(np.asarray(packed), np.asarray(want),
                               rtol=0, atol=1e-5)
    # the clipped read-modify-write applies the summed Δw once
    w = jax.random.uniform(ks[4], (n_pre, n_post))
    got = weight_update_packed(w, pre_s, post_s, pre_w, post_w, p,
                               depth=depth, eta=0.5, **kw)
    np.testing.assert_allclose(np.asarray(got),
                               np.clip(np.asarray(w) + 0.5 * np.asarray(want),
                                       0.0, 1.0),
                               rtol=0, atol=1e-5)


def test_interpret_default_derives_from_host():
    """The ops wrappers' interpret default comes from the dispatch layer:
    on CPU it resolves to the interpreter (the only thing that runs), on an
    accelerator it must resolve to the compiled kernel — selecting the
    fused path can never silently mean interpreter mode on real hardware."""
    from repro.kernels.dispatch import (default_fused_backend,
                                        default_interpret, resolve_backend)
    assert default_interpret() == resolve_backend(default_fused_backend())[1]
    if jax.default_backend() == "cpu":
        assert default_fused_backend() == "fused_interpret"
        assert default_interpret() is True
    else:  # pragma: no cover - accelerator hosts only
        assert default_fused_backend() == "fused"
        assert default_interpret() is False


def test_ops_wrappers_run_with_derived_interpret_default(key):
    """Omitting ``interpret`` is safe on this host (derived, not hardcoded)."""
    from repro.core.history import pack_words
    from repro.kernels.itp_stdp.ops import weight_update_packed
    n = 16
    pre_h, post_h = _rolled_histories(key, n, n, 7, steps=3)
    out = weight_update_packed(
        jnp.full((n, n), 0.5), jnp.ones((n,)), jnp.zeros((n,)),
        pack_words(pre_h), pack_words(post_h), STDPParams(), depth=7)
    assert out.shape == (n, n)


# ---------------------------------------------------------------------------
# LIF kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,n", [
    (1, 128), (3, 100),
    pytest.param(8, 512, marks=pytest.mark.slow),
    pytest.param(16, 1024, marks=pytest.mark.slow),
])
def test_lif_kernel_vs_ref(key, b, n):
    from repro.kernels.lif.ops import lif_step_kernel
    p = LIFParams(tau=2.0, v_th=0.7)
    v = jax.random.uniform(key, (b, n), minval=-0.5, maxval=1.2)
    i_in = jax.random.uniform(jax.random.fold_in(key, 1), (b, n),
                              maxval=0.8)
    st = LIFState(v=v)
    s1, sp1 = lif_step_kernel(st, i_in, p, use_kernel=True, interpret=True)
    s2, sp2 = lif_step(st, i_in, p)
    np.testing.assert_allclose(np.asarray(s1.v), np.asarray(s2.v),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(sp1), np.asarray(sp2))


def test_lif_kernel_1d_api(key):
    from repro.kernels.lif.ops import lif_step_kernel
    p = LIFParams()
    st = lif_init((40,), p)
    i_in = jax.random.uniform(key, (40,))
    s1, sp1 = lif_step_kernel(st, i_in, p, use_kernel=True, interpret=True)
    assert s1.v.shape == (40,) and sp1.shape == (40,)


# ---------------------------------------------------------------------------
# po2 quantiser kernel
# ---------------------------------------------------------------------------

@settings(max_examples=100, deadline=None)
@given(x=st.floats(-1e6, 1e6, allow_nan=False, width=32))
def test_po2_roundtrip_properties(x):
    from repro.kernels.po2_quant.ref import po2_roundtrip_ref
    q = float(po2_roundtrip_ref(jnp.asarray(x, jnp.float32)))
    if x == 0.0 or abs(x) < 1.2e-38:   # zero / f32-subnormal underflow → 0
        assert q == 0.0 or np.sign(q) == np.sign(x)
    else:
        assert np.sign(q) == np.sign(x)
        if 1e-15 < abs(x) < 1e15:   # in exponent range
            # nearest po2 in log space: ratio within [2^-0.5, 2^0.5]
            ratio = q / x
            assert 0.7071 / 1.001 <= ratio <= 1.4143 * 1.001
            # q is an exact power of two
            m, e = np.frexp(abs(q))
            assert m == 0.5


@pytest.mark.parametrize("n", [
    128, 500, pytest.param(4096, marks=pytest.mark.slow)])
def test_po2_kernel_vs_ref(key, n):
    from repro.kernels.po2_quant.kernel import po2_decode, po2_encode
    from repro.kernels.po2_quant.ref import po2_decode_ref, po2_encode_ref
    x = jax.random.normal(key, (n,)) * jnp.exp(
        jax.random.uniform(jax.random.fold_in(key, 1), (n,), minval=-20,
                           maxval=20))
    pad = (-n) % 128
    xp = jnp.pad(x, (0, pad))
    enc_k = po2_encode(xp, tile=128, interpret=True)[:n]
    enc_r = po2_encode_ref(x)
    np.testing.assert_array_equal(np.asarray(enc_k), np.asarray(enc_r))
    dec_k = po2_decode(jnp.pad(enc_r, (0, pad)), tile=128,
                       interpret=True)[:n]
    dec_r = po2_decode_ref(enc_r)
    np.testing.assert_allclose(np.asarray(dec_k), np.asarray(dec_r))


def test_po2_quantize_tree(key):
    from repro.kernels.po2_quant.ops import po2_quantize_tree
    tree = {"a": jax.random.normal(key, (37,)),
            "b": {"c": jax.random.normal(jax.random.fold_in(key, 1), (8, 9))}}
    out = po2_quantize_tree(tree)
    for leaf in jax.tree_util.tree_leaves(out):
        vals = np.abs(np.asarray(leaf))
        nz = vals[vals > 0]
        m, _ = np.frexp(nz)
        assert (m == 0.5).all()


def test_po2_quantize_kernel_path(key):
    from repro.kernels.po2_quant.ops import po2_quantize
    x = jax.random.normal(key, (77,))
    a = po2_quantize(x, use_kernel=True, interpret=True)
    b = po2_quantize(x, use_kernel=False)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b))
