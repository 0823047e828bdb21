"""The program's profiler spans and device scopes (``repro.tracing``).

Host spans are read back from a CPU trace of the JAX profiler, where each
host thread is one line of the host plane.  Device scopes are read from the
``op_name`` metadata of the compiled ``run_snn`` (the Pallas kernels in
interpret mode here; ``test_tpu_compile.py`` checks the chip's text), where
every nested jit is inlined, so each op carries its whole name stack.
"""
import functools
import glob
import re

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from repro import tracing
from repro.data import pipeline, synthetic_digits
from repro.models import snn

OP_NAME = re.compile(r'op_name="([^"]*)"')
# the pallas_call names of the packed kernels the paper networks run
KERNELS = {"itp_stdp_update_packed", "itp_stdp_conv_delta_packed"}


@pytest.fixture
def traced(tmp_path):
    """Run ``fn`` under the profiler; return the host plane's lines (one
    per thread) as lists of ``(name, start_ns, end_ns)``."""

    def run(fn):
        jax.profiler.start_trace(str(tmp_path))
        try:
            fn()
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
        return [[(e.name, e.start_ns, e.end_ns) for e in line.events]
                for plane in ProfileData.from_file(path).planes
                if plane.name.startswith("/host:CPU") for line in plane.lines]

    return run


def _named(line, name):
    return [e for e in line if e[0] == name]


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def _threads_with(lines, name):
    return {i for i, line in enumerate(lines) if _named(line, name)}


def test_reset_dynamics_spans_nest_on_one_thread(traced):
    """The reset's fresh state is built once per ``(cfg, batch)``, inside
    the first reset; the second builds nothing and reads nothing back."""
    cfg = snn.fmnist_dcsnn("itp")
    state = snn.init_snn(jax.random.PRNGKey(0), cfg, 2)
    snn._reset_layers.cache_clear()

    def two_resets():
        first = snn.reset_dynamics(state, cfg, 2)
        jax.block_until_ready(snn.reset_dynamics(first, cfg, 2))

    lines = traced(two_resets)
    (i,) = _threads_with(lines, tracing.RESET_DYNAMICS)
    first, second = sorted(_named(lines[i], tracing.RESET_DYNAMICS), key=lambda e: e[1])
    (build,) = _named(lines[i], tracing.FRESH_STATE)
    assert _inside(build, first) and not _inside(build, second)
    assert _threads_with(lines, tracing.FRESH_STATE) == {i}
    # a reset neither draws weights nor reads anything back
    assert not _threads_with(lines, tracing.INIT_SNN)
    assert not _threads_with(lines, "snn.host_sync")


def test_prefetcher_spans_sit_on_the_producer_thread(traced):
    def consume():
        raw = pipeline.spike_stream(jax.random.PRNGKey(1), synthetic_digits,
                                    batch=2, t_steps=3)
        with pipeline.Prefetcher(raw, depth=2) as stream, TraceAnnotation("consumer"):
            for _ in range(4):
                jax.block_until_ready(next(stream)["spikes"])

    lines = traced(consume)
    (consumer,) = _threads_with(lines, "consumer")
    producer = _threads_with(lines, tracing.SAMPLE)
    assert len(producer) == 1 and consumer not in producer
    for name in (tracing.ENCODE, tracing.PREFETCH_PUT):
        assert _threads_with(lines, name) == producer
    (p,) = producer
    assert len(_named(lines[p], tracing.PREFETCH_PUT)) >= 4
    # the consumer's waits, where it had to wait, are on its own thread
    assert _threads_with(lines, tracing.PREFETCH_WAIT_ITEM) <= {consumer}


@functools.lru_cache(maxsize=None)
def _scope_paths(net: str, train: bool) -> list[set]:
    cfg = snn.PAPER_NETWORKS[net]("itp", backend="fused_interpret")
    state = snn.init_snn(jax.random.PRNGKey(0), cfg, 2)
    n_in = 1
    for d in cfg.input_shape:
        n_in *= d
    raster = jax.ShapeDtypeStruct((2, 2, n_in), jnp.uint8)
    text = snn.run_snn.lower(state, raster, cfg, train=train).compile().as_text()
    return [set(name.split("/")) for name in OP_NAME.findall(text)]


@pytest.mark.parametrize("train", [True, False], ids=["train", "eval"])
@pytest.mark.parametrize("net", ["2layer-snn", "6layer-dcsnn", "5layer-csnn"])
def test_run_snn_ops_carry_the_device_scopes(net, train):
    paths = _scope_paths(net, train)
    seen = set().union(*paths) & set(tracing.DEVICE_SCOPES)
    want = {tracing.FORWARD, tracing.TIMING} | ({tracing.UPDATE} if train else set())
    assert seen == want
    kernels = [p for p in paths if p & KERNELS]
    assert bool(kernels) == train
    assert all(tracing.UPDATE in p for p in kernels)


@pytest.mark.parametrize("train", [True, False], ids=["train", "eval"])
@pytest.mark.parametrize("net", ["2layer-snn", "6layer-dcsnn", "5layer-csnn"])
def test_window_scope_marks_the_conv_gather_of_words(net, train):
    """``stdp.window`` is on the im2col gather of the packed words, which
    only a conv layer's update has, and always nested in ``stdp.timing``."""
    window = [p for p in _scope_paths(net, train) if tracing.WINDOW in p]
    assert bool(window) == (train and net != "2layer-snn")
    assert all(tracing.TIMING in p for p in window)
    assert tracing.WINDOW not in tracing.DEVICE_SCOPES
