"""Synthetic data generators + the spike-encoding pipeline."""
import jax.numpy as jnp
import numpy as np

from repro.data import (Prefetcher, encode_batch, spike_stream,
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
