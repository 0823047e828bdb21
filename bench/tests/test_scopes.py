"""The scope readers on short excerpts recorded on a TPU v5e.

``data/scoped_<workload>.json`` holds the device ops, the host spans (the
harness's and the program's own) and the program scope of each op named
there, for the first milliseconds of a traced window, written by
``record_scoped.py``.  Each reader is checked against a brute-force count
on a 1 µs grid, and the scope map against the program compiled here.
"""
import importlib
import json
import types
from pathlib import Path

import numpy as np
import pytest

import trace_reduce as tr
from metrics import _scopes

DATA = Path(__file__).resolve().parent / "data"
SCOPED = sorted(DATA.glob("scoped_*.json"))
WORKLOADS = {"snn2.train-b16", "dcsnn.train-b256"}
READERS = {"forward_device_ms": _scopes.FORWARD, "timing_device_ms": _scopes.TIMING,
           "update_device_ms": _scopes.UPDATE}


def _load(path):
    with open(path) as f:
        d = json.load(f)
    d["devices"] = {int(k): [tuple(e) for e in v] for k, v in d["devices"].items()}
    return d


def _grid(events, t0, t1, step=1000.0):
    n = int((t1 - t0) // step) + 1
    busy = np.zeros(n, bool)
    for _, s, e in events:
        busy[int((s - t0) // step):int(np.ceil((e - t0) / step))] = True
    return busy.sum() * step


def _run(d, monkeypatch):
    """A ``--trace 1`` run's reader input over the excerpt, with the scope map
    it recorded in place of one compiled for the chip."""
    monkeypatch.setattr(_scopes, "_compiled_map", lambda *a: d["scopes"])
    return {"trace": {"ops": d["devices"][0]}, "config": {},
            "traffic": {"mode": "train", "batch": 0, "t_steps": 0},
            "window": types.SimpleNamespace(rasters=d["rasters"])}


def test_scoped_excerpts_exist():
    assert {p.stem[len("scoped_"):] for p in SCOPED} >= WORKLOADS


@pytest.mark.parametrize("reader", sorted(READERS))
@pytest.mark.parametrize("path", SCOPED, ids=lambda p: p.stem)
def test_reader_matches_grid_count(path, reader, monkeypatch):
    d = _load(path)
    got = importlib.import_module(f"metrics.{reader}").read(_run(d, monkeypatch))
    ev = [e for e in d["devices"][0] if d["scopes"].get(e[0]) == READERS[reader]]
    assert ev and d["rasters"] > 0
    grid = _grid(ev, d["t0"], d["t1"]) / 1e6 / d["rasters"]
    # the grid rounds every edge out to a microsecond
    assert got <= grid <= got + 2e-3 * len(ev) / d["rasters"]


@pytest.mark.parametrize("path", SCOPED, ids=lambda p: p.stem)
def test_scopes_fit_in_busy_time(path):
    d = _load(path)
    ops = d["devices"][0]
    parts = [_scopes.device_ms(ops, d["scopes"], s, d["rasters"]) for s in _scopes.SCOPES]
    busy = tr.union_ns(ops) / 1e6 / d["rasters"]
    assert all(p > 0 for p in parts) and sum(parts) <= busy * (1 + 1e-9)


@pytest.mark.parametrize("path", SCOPED, ids=lambda p: p.stem)
def test_update_kernels_and_history_reverses_have_their_scopes(path):
    d = _load(path)
    names = {e[0] for e in d["devices"][0]}
    kernels = tr.kernel_events([(n, 0, 0) for n in names],
                               r"itp_stdp_update_packed|itp_stdp_conv_delta_packed")
    assert kernels and all(d["scopes"].get(n) == _scopes.UPDATE for n, _, _ in kernels)
    revs = [n for n in names if n.startswith("%rev")]
    assert revs and all(d["scopes"].get(n) == _scopes.TIMING for n in revs)


@pytest.mark.parametrize("op_name,scope", [
    ("jit(run_snn)/while/body/closed_call/snn.forward/mul", _scopes.FORWARD),
    ("jit(run_snn)/while/body/closed_call/stdp.update/stdp.timing/rev", _scopes.TIMING),
    ("jit(run_snn)/while/body/stdp.update/vmap(jit(itp_stdp_update_packed))/pallas_call",
     _scopes.UPDATE),
    ("jit(run_snn)/while/body/dynamic_slice", None),
    ("jit(run_snn)/while/body/snn.forwarding/mul", None),
])
def test_innermost_scope(op_name, scope):
    assert _scopes.innermost(op_name) == scope


def test_a_program_without_scopes_reads_nothing():
    text = '  %fusion.3 = f32[4]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(f)/mul"}\n'
    assert _scopes.scope_map(text) == {}
    assert _scopes.device_ms([("%fusion.3", 0.0, 1e6)], {}, _scopes.FORWARD, 1) is None


@pytest.mark.parametrize("workload,train", [("snn2.train-b16", True),
                                            ("dcsnn.train-b256", True),
                                            ("snn2.infer-b256", False)])
def test_compiled_map_holds_the_cell_scopes(workload, train):
    """The map the readers compile, for the cell's program in interpret mode."""
    import harness

    c = dict(harness.load_cell(workload)["config"], backend="fused_interpret")
    scopes = _scopes._compiled_map(json.dumps(c, sort_keys=True), train, 2, 2)
    want = {_scopes.FORWARD, _scopes.TIMING} | ({_scopes.UPDATE} if train else set())
    assert set(scopes.values()) == want
