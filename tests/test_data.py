"""Synthetic data generators + the spike-encoding pipeline."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.encoding import minmax_normalise, rate_code
from repro.data import (Prefetcher, encode_batch, pipeline, spike_stream,
                        synthetic_digits, synthetic_fashion, synthetic_fault)


def test_digits_shapes_and_range(key):
    x, y = synthetic_digits(key, 32)
    assert x.shape == (32, 28, 28)
    assert float(x.min()) >= 0.0 and float(x.max()) <= 1.0
    assert set(np.asarray(y)) <= set(range(10))


def test_digits_class_structure(key):
    """Same-class images correlate more than cross-class ones."""
    x, y = synthetic_digits(key, 200, noise=0.05, jitter=0)
    x = np.asarray(x).reshape(200, -1)
    y = np.asarray(y)
    same, diff = [], []
    for c in range(10):
        m = x[y == c]
        if len(m) >= 2:
            same.append(np.corrcoef(m[0], m[1])[0, 1])
    for c in range(5):
        a, b = x[y == c], x[y == (c + 5) % 10]
        if len(a) and len(b):
            diff.append(np.corrcoef(a[0], b[0])[0, 1])
    assert np.mean(same) > np.mean(diff) + 0.2


def test_fashion_and_fault_shapes(key):
    x, y = synthetic_fashion(key, 8)
    assert x.shape == (8, 28, 28)
    x, y = synthetic_fault(key, 8, length=256, channels=2)
    assert x.shape == (8, 256, 2)
    assert set(np.asarray(y)) <= set(range(4))


def test_fault_classes_differ_spectrally(key):
    x, y = synthetic_fault(key, 400, noise=0.02)
    x, y = np.asarray(x), np.asarray(y)
    # class 3 (bearing impulses) has the heaviest kurtosis
    def kurt(v):
        v = v - v.mean()
        return (v ** 4).mean() / (v ** 2).mean() ** 2
    k3 = np.mean([kurt(x[i, :, 0]) for i in np.where(y == 3)[0][:20]])
    k0 = np.mean([kurt(x[i, :, 0]) for i in np.where(y == 0)[0][:20]])
    assert k3 > k0


def test_encode_batch_rate(key):
    x = jnp.stack([jnp.zeros((10,)), jnp.linspace(0, 1, 10)])
    s = encode_batch(key, x, 800)
    assert s.shape == (800, 2, 10)
    # max-value element fires ≈ every step, zero never
    rates = np.asarray(s.mean(axis=0))
    assert rates[1, -1] > 0.95
    assert rates[1, 0] < 0.05


def test_spike_stream(key):
    it = spike_stream(key, lambda k, n: synthetic_digits(k, n),
                      batch=4, t_steps=6, n_steps=3)
    batches = list(it)
    assert len(batches) == 3
    assert batches[0]["spikes"].shape == (6, 4, 784)
    assert batches[0]["labels"].shape == (4,)


def _eager_encode(k, x, t_steps):
    return rate_code(k, minmax_normalise(x.reshape(x.shape[0], -1), axis=-1),
                     t_steps)


def _samples(key, batch, shape):
    """Rows of different offsets and scales; the last row constant when
    there are several, so the min-max's floor on the range is taken."""
    k1, k2, k3 = jax.random.split(key, 3)
    x = jax.random.normal(k1, (batch, *shape))
    scale = jax.random.uniform(k2, (batch,) + (1,) * len(shape), minval=0.1, maxval=9.0)
    shift = jax.random.normal(k3, (batch,) + (1,) * len(shape))
    x = x * scale + shift
    return x.at[-1].set(0.25) if batch > 1 else x


@pytest.mark.parametrize("batch", [1, 16, 256])
@pytest.mark.parametrize("shape", [(28, 28), (512, 2)], ids=["image", "window"])
def test_encode_batch_equals_eager_composition(key, shape, batch):
    x = _samples(key, batch, shape)
    k_enc = jax.random.fold_in(key, 7)
    got = encode_batch(k_enc, x, 3)
    want = _eager_encode(k_enc, x, 3)
    assert got.shape == want.shape == (3, batch, shape[0] * shape[1])
    assert got.dtype == want.dtype == jnp.uint8
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_encode_batch_keeps_the_normalisation_bits(key):
    """Inputs whose normalised values land on their own uniform draws: a
    one-ulp change of the min-max arithmetic (a reciprocal multiply in place
    of the divide, say) flips spikes that random inputs would not."""
    k_enc = jax.random.fold_in(key, 5)
    u = jax.random.uniform(k_enc, (1, 64, 784))[0]
    lo, r = -1.7, 3.3
    x = (lo + u * r).at[:, 0].set(lo).at[:, 1].set(lo + r)
    on_edge = minmax_normalise(x, axis=-1) == u
    assert int(on_edge.sum()) > 1000
    np.testing.assert_array_equal(np.asarray(encode_batch(k_enc, x, 1)),
                                  np.asarray(_eager_encode(k_enc, x, 1)))


@jax.jit
def _take(x, y, k):
    i = jax.random.randint(k, (4,), 0, x.shape[0])
    return x[i], y[i]


def test_spike_stream_equals_eager_loop(key):
    """The compiled split and encode give the rasters and labels of the
    plain loop: eager split, the sampler, the eager encode."""
    x_pool, y_pool = synthetic_digits(jax.random.PRNGKey(3), 64)
    sampler = lambda k, n: _take(x_pool, y_pool, k)  # noqa: E731
    got = list(spike_stream(key, sampler, batch=4, t_steps=5, n_steps=5))
    assert len(got) == 5
    k = key
    for b in got:
        k, k_data, k_enc = jax.random.split(k, 3)
        x, labels = sampler(k_data, 4)
        np.testing.assert_array_equal(np.asarray(b["spikes"]),
                                      np.asarray(_eager_encode(k_enc, x, 5)))
        np.testing.assert_array_equal(np.asarray(b["labels"]), np.asarray(labels))


def test_encode_batch_compiles_once_per_shape(key):
    """Fresh keys and values at one shape reuse one compiled program: a
    retrace per raster would be a compile inside a measured window."""
    x = _samples(key, 5, (7, 3))
    before = pipeline._encode._cache_size()
    for i in range(10):
        encode_batch(jax.random.fold_in(key, i), x * (i + 1), 9)
    n_encode = pipeline._encode._cache_size()
    assert n_encode == before + 1
    # the stream's split compiles once too, and its encode reuses the program
    before = pipeline._split3._cache_size()
    for _ in spike_stream(jax.random.PRNGKey(11), lambda k, n: (x, k),
                          batch=5, t_steps=9, n_steps=10):
        pass
    assert pipeline._split3._cache_size() <= before + 1
    assert pipeline._encode._cache_size() == n_encode


def test_prefetcher_preserves_order():
    it = iter([{"i": i} for i in range(20)])
    pf = Prefetcher(it, depth=3)
    out = [int(b["i"]) for b in pf]
    assert out == list(range(20))


def test_prefetcher_matches_unprefetched_spike_stream(key):
    """Prefetching is a pure latency optimisation: same batches, same order."""
    sampler = lambda k, n: synthetic_digits(k, n)  # noqa: E731
    plain = list(spike_stream(key, sampler, batch=4, t_steps=6, n_steps=5))
    with Prefetcher(spike_stream(key, sampler, batch=4, t_steps=6,
                                 n_steps=5)) as pf:
        fetched = list(pf)
    assert len(fetched) == len(plain)
    for a, b in zip(plain, fetched):
        np.testing.assert_array_equal(np.asarray(a["spikes"]),
                                      np.asarray(b["spikes"]))
        np.testing.assert_array_equal(np.asarray(a["labels"]),
                                      np.asarray(b["labels"]))


def test_prefetcher_close_shuts_down_cleanly():
    """Early abandonment must stop the fill thread, not leak it."""
    def slow_source():
        for i in range(1000):
            yield {"i": i}

    pf = Prefetcher(slow_source(), depth=2)
    first = next(pf)
    assert int(first["i"]) == 0
    pf.close()
    assert not pf._thread.is_alive()
    # idempotent, and a closed prefetcher raises StopIteration not a hang
    pf.close()
    assert list(pf) == []
