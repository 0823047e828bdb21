"""The benchmark's side of a run: cells, the program under test, its loops.

Everything a cell needs is found by name: the workload in
``BENCHMARK.json`` names a configuration (``configs/<name>.json``) and a
traffic mix (``traffic/<name>.json``); the limits of its correctness
check are in ``limits/<workload>.json``, beside the readings they were
set from.  From the program the harness
takes the network maker, ``init_snn``, ``run_snn``, ``reset_dynamics``,
the data generator and the input pipeline (``spike_stream``,
``encode_batch``, ``Prefetcher``), and drives them call for call as
``repro.train.stdp_trainer`` does.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import math
import sys
import threading
import time
from functools import partial
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.data import pipeline  # noqa: E402
from repro.launch import cli  # noqa: E402
from repro.models import snn  # noqa: E402

POOL_KEY, STREAM_KEY, INIT_KEY = 1, 2, 3


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str) -> dict:
    """The workload entry with its configuration, traffic and limits."""
    spec = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    return {
        "workload": w,
        "config": load_json(ROOT / configs[w["config"]]["file"]),
        "traffic": load_json(BENCH / "traffic" / f"{w['traffic']}.json"),
        "limits": load_json(BENCH / "limits" / f"{workload}.json")["limits"],
        "spec": spec,
    }


def seed_key(seed: int) -> jax.Array:
    """A key from a seed of any size up to 64 bits."""
    if not 0 <= seed < 1 << 64:
        raise SystemExit(f"--seed must lie in [0, 2**64), got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


# ---------------------------------------------------------------------------
# the program's configuration, checked against the file
# ---------------------------------------------------------------------------

def program_config(c: dict, **override) -> snn.SNNConfig:
    """The config of the maker the file names (one of the program's
    paper-network functions, by its function name) with the file's
    settings, each verified.

    Every setting the file states must be what the program runs; a
    maker default that drifts from the file stops the run.
    """
    makers = {f.__name__: f for f in snn.PAPER_NETWORKS.values()}
    if c["maker"] not in makers:
        raise SystemExit(f"{c['name']}: the program has no network maker {c['maker']!r}; "
                         f"known: {sorted(makers)}")
    maker = makers[c["maker"]]
    kw = {"backend": c["backend"], "packed_history": c["packed_history"],
          "quantise": c["quantise"]}
    kw.update(override)
    cfg = maker(c["rule"], **kw)
    layers = tuple(
        snn.SNNLayerSpec(kind=l["kind"], out_features=l.get("out_features", 0),
                         kernel=l.get("kernel", 3), stride=l.get("stride", 1),
                         pool=l.get("pool", 2))
        for l in c["layers"])
    want = {
        "input_shape": tuple(c["input_shape"]), "layers": layers,
        "neuron": c["neuron"], "rule": c["rule"], "depth": c["depth"],
        "pairing": c["pairing"], "eta": c["eta"], "gain": c["gain"],
        "izhi_gain": c["izhi_gain"], "w_bits": c["w_bits"],
        "inhibition": c["inhibition"], "hard_wta": c["hard_wta"],
        "theta_plus": c["theta_plus"], "theta_tau": c["theta_tau"],
        "compensate": c["compensate"],
    }
    for key, val in want.items():
        got = getattr(cfg, key)
        if got != val:
            raise SystemExit(f"{c['name']}: program runs {key}={got!r}, file states {val!r}")
    for group in ("stdp", "lif", "izhi"):
        got = dataclasses.asdict(getattr(cfg, group))
        if got != c[group]:
            raise SystemExit(f"{c['name']}: program runs {group}={got}, file states {c[group]}")
    return cfg


def forward_precision(c: dict):
    """The context the file's forward-current precision asks for."""
    p = c["precision"]["forward_current"]
    return jax.default_matmul_precision(None if p == "default" else p)


# ---------------------------------------------------------------------------
# data: a device-resident pool drawn from the seed
# ---------------------------------------------------------------------------

def make_pool(key: jax.Array, c: dict, n: int) -> tuple[jax.Array, jax.Array]:
    """``n`` samples of the file's sampler, each of the file's input shape
    (a unit channel the sampler leaves out aside); a sampler that drifts
    from the file stops the run."""
    gen, _ = cli.sampler_for(c["data"]["sampler"])
    x, y = jax.jit(gen, static_argnums=1)(key, n)
    if _unit_dims_dropped(x.shape[1:]) != _unit_dims_dropped(c["input_shape"]):
        raise SystemExit(f"{c['name']}: the sampler {c['data']['sampler']!r} gives samples "
                         f"of shape {tuple(x.shape[1:])}, file states input_shape "
                         f"{tuple(c['input_shape'])}")
    return x, y


def _unit_dims_dropped(shape) -> tuple:
    return tuple(d for d in shape if d != 1)


def pool_indices(k: jax.Array, n: int, size: int) -> jax.Array:
    return jax.random.randint(k, (n,), 0, size)


def pool_sampler(pool: tuple[jax.Array, jax.Array]):
    """sampler(key, n): ``n`` rows of the pool, drawn from ``key``."""
    x, y = pool
    return lambda k, n: _take(x, y, k, n)


@partial(jax.jit, static_argnums=3)
def _take(x, y, k, n):
    i = pool_indices(k, n, x.shape[0])
    return x[i], y[i]


# ---------------------------------------------------------------------------
# compile watch
# ---------------------------------------------------------------------------

class CompileWatch:
    """Counts compilations and compile-cache reads while ``armed``."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        self.armed = False
        self.count = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if self.armed and event in self.EVENTS:
            with self._lock:
                self.count += 1


# ---------------------------------------------------------------------------
# the loops
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Window:
    seconds: float = 0.0
    rasters: int = 0
    samples: int = 0
    data_wait_s: float = 0.0
    host_loop_s: float = 0.0
    latencies_s: list = dataclasses.field(default_factory=list)
    ends_s: list = dataclasses.field(default_factory=list)
    gc_s: float = 0.0
    compiles: int = 0


class Trainer:
    """The feature-learning loop of ``stdp_trainer.train_to_accuracy``.

    One object: the program state, the prefetched stream, the config.
    ``step`` is the loop body (``next(stream)``, ``run_snn(train=True)``,
    ``reset_dynamics``); set-up drives the first rasters through it and
    the window continues with the same object.
    """

    def __init__(self, cfg: snn.SNNConfig, state, stream_key, sampler, traffic: dict):
        self.cfg = cfg
        self.state = state
        self.batch = traffic["batch"]
        raw = pipeline.spike_stream(stream_key, sampler, batch=self.batch,
                                    t_steps=traffic["t_steps"])
        self.stream = pipeline.Prefetcher(raw, depth=traffic["prefetch_depth"])

    def step(self, win: Window | None = None, record: list | None = None):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("data_wait"):
            b = next(self.stream)
        t1 = time.perf_counter()
        with jax.profiler.TraceAnnotation("host_loop"):
            before = self.state
            state, counts = snn.run_snn(self.state, b["spikes"], self.cfg, train=True)
            if record is not None:
                record.append({"spikes": b["spikes"], "w_before": before.weights,
                               "w_after": state.weights, "counts": counts,
                               "layers": state.layers})
            self.state = snn.reset_dynamics(state, self.cfg, self.batch)
        t2 = time.perf_counter()
        if win is not None:
            win.data_wait_s += t1 - t0
            win.host_loop_s += t2 - t1
            win.rasters += 1

    def drain(self) -> None:
        jax.block_until_ready(self.state.weights)

    def close(self) -> None:
        self.stream.close()


class Classifier:
    """The held-out pass of ``stdp_trainer._collect_counts``.

    Per batch: the pool sampler, ``encode_batch``, ``reset_dynamics``,
    ``run_snn(train=False)``, and the counts on the host before the next
    batch is submitted.
    """

    def __init__(self, cfg: snn.SNNConfig, state, key, sampler, traffic: dict):
        self.cfg = cfg
        self.state = state
        self.key = key
        self.sampler = sampler
        self.batch = traffic["batch"]
        self.t_steps = traffic["t_steps"]
        self.keys: list = []
        self.counts: list = []

    def step(self, win: Window | None = None):
        t0 = time.perf_counter()
        self.key, k_data, k_enc = jax.random.split(self.key, 3)
        with jax.profiler.TraceAnnotation("data_wait"):
            x, _ = self.sampler(k_data, self.batch)
            spikes = pipeline.encode_batch(k_enc, x, self.t_steps)
        t1 = time.perf_counter()
        with jax.profiler.TraceAnnotation("host_loop"):
            self.state = snn.reset_dynamics(self.state, self.cfg, self.batch)
            self.state, counts = snn.run_snn(self.state, spikes, self.cfg, train=False)
        with jax.profiler.TraceAnnotation("result_wait"):
            host = np.asarray(counts)
        t2 = time.perf_counter()
        self.keys.append((k_data, k_enc))
        self.counts.append(host.astype(np.uint8))
        if win is not None:
            win.data_wait_s += t1 - t0
            win.host_loop_s += t2 - t1
            win.latencies_s.append(t2 - t0)
            win.rasters += 1

    def drain(self) -> None:
        jax.block_until_ready(self.state.weights)

    def close(self) -> None:
        pass


class _GcTimer:
    """Adds the seconds of the interpreter's garbage collections to a window."""

    def __init__(self, win: Window):
        self.win = win
        self.t: float | None = None

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self.t = time.perf_counter()
        elif self.t is not None:
            self.win.gc_s += time.perf_counter() - self.t
            self.t = None


class _TracedSlice:
    """The part of a window that a ``--trace 1`` run reduces.

    The profiler starts with the window; the slice (host span
    ``window``) begins at ``start``, once every raster submitted before
    it has completed, and ends ``seconds`` later or with the window,
    again once its rasters have completed, and the profiler with it.  So
    the profiler's own start-up stays out of the slice, and the slice's
    counts are those of exactly the device work it holds.
    """

    def __init__(self, loop, trace_dir: str, start: float, seconds: float):
        # the Python tracer would time every Python call and about halve the
        # host-bound loops' rate; the harness's own spans need only the host tracer
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        self.tracing = True
        self.loop, self.start, self.seconds = loop, start, seconds
        self.ann = None
        self.base: Window | None = None
        self.result: Window | None = None

    def poll(self, win: Window) -> None:
        now = time.perf_counter()
        if self.tracing and self.ann is None and now >= self.start:
            self.loop.drain()
            self.base = dataclasses.replace(win, latencies_s=list(win.latencies_s))
            self.t0 = time.perf_counter()
            self.ann = jax.profiler.TraceAnnotation("window")
            self.ann.__enter__()
        elif self.ann is not None and now >= self.t0 + self.seconds:
            self.stop(win)

    def stop(self, win: Window) -> None:
        if self.ann is not None:
            self.loop.drain()
            b = self.base
            self.result = Window(
                seconds=time.perf_counter() - self.t0, rasters=win.rasters - b.rasters,
                data_wait_s=win.data_wait_s - b.data_wait_s,
                host_loop_s=win.host_loop_s - b.host_loop_s,
                latencies_s=win.latencies_s[len(b.latencies_s):])
            self.ann.__exit__(None, None, None)
            self.ann = None
        if self.tracing:
            jax.profiler.stop_trace()
            self.tracing = False


def run_window(loop, seconds: float, watch: CompileWatch, *, trace_dir: str | None = None,
               trace_lead: float = 0.0, trace_seconds: float = 0.0) -> Window:
    """Drive ``loop.step`` for ``seconds``; the window ends when the last
    raster submitted has completed.

    With ``trace_dir`` the returned counts are those of the traced slice
    (``_TracedSlice``: from ``trace_lead`` seconds into the window, for
    ``trace_seconds``); the rest of the window runs on untraced, so a
    traced run offers the same load for as long.
    """
    win = Window()
    gc_timer = _GcTimer(win)
    gc.callbacks.append(gc_timer)
    watch.count = 0
    watch.armed = True
    t0 = time.perf_counter()
    deadline = t0 + seconds
    traced = _TracedSlice(loop, trace_dir, t0 + trace_lead, trace_seconds) if trace_dir else None
    try:
        while time.perf_counter() < deadline:
            loop.step(win)
            win.ends_s.append(time.perf_counter() - t0)
            if traced:
                traced.poll(win)
        loop.drain()
    finally:
        if traced:
            traced.stop(win)
        gc.callbacks.remove(gc_timer)
    win.seconds = time.perf_counter() - t0
    watch.armed = False
    win.compiles = watch.count
    out = win
    if traced:
        if traced.result is None:
            raise SystemExit("bench: the window ended before its traced slice began")
        out = traced.result
    out.compiles = win.compiles
    out.gc_s = win.gc_s
    out.ends_s = win.ends_s
    out.samples = out.rasters * loop.batch
    return out


def per_second(ends_s: list) -> list[int]:
    """Rasters whose host step ended in each second of the window."""
    n = [0] * (int(max(ends_s, default=0.0)) + 1)
    for t in ends_s:
        n[int(t)] += 1
    return n


def percentile(values: list, q: float) -> float:
    """The q-th percentile by the nearest-rank rule."""
    v = sorted(values)
    return v[max(0, math.ceil(q / 100.0 * len(v)) - 1)]
