"""The trace reduction on short excerpts recorded on a TPU v5e.

``data/trace_<workload>.json`` holds the device ops and the harness's
host spans of the first milliseconds of a traced window, written by
``record_trace.py``.  The reduction is checked against a brute-force
count on a 1 µs grid, and the kernels the roofline metrics read must be
found by their names.
"""
import json
import time
from pathlib import Path

import numpy as np
import pytest

import trace_reduce as tr
from costs import snn as costs

DATA = Path(__file__).resolve().parent / "data"
TRACES = sorted(DATA.glob("trace_*.json"))
KERNELS = {
    "snn2.train-b16": ["itp_stdp_update_packed"],
    "dcsnn.train-b256": ["itp_stdp_update_packed", "itp_stdp_conv_delta_packed"],
}


def _load(path):
    with open(path) as f:
        d = json.load(f)
    d["devices"] = {int(k): [tuple(e) for e in v] for k, v in d["devices"].items()}
    d["host"] = [tuple(h) for h in d["host"]]
    return d


def _grid_busy(events, t0, t1, step=1000.0):
    n = int((t1 - t0) // step) + 1
    busy = np.zeros(n, bool)
    for _, s, e in events:
        busy[int((s - t0) // step):int(np.ceil((e - t0) / step))] = True
    return busy.sum() * step


def test_recorded_traces_exist():
    assert {p.stem[len("trace_"):] for p in TRACES} >= set(KERNELS)


@pytest.mark.parametrize("path", TRACES, ids=lambda p: p.stem)
def test_busy_matches_grid_count(path):
    d = _load(path)
    red = tr.reduce(d, d["t0"], d["t1"], ("data_wait", "host_loop"))
    ops = d["devices"][0]
    assert 0 < red["busy_s"] <= red["window_s"]
    grid = _grid_busy(ops, d["t0"], d["t1"]) / 1e9
    # the grid rounds every edge out to a microsecond
    assert red["busy_s"] <= grid <= red["busy_s"] + 2e-6 * len(ops)


@pytest.mark.parametrize("path", TRACES, ids=lambda p: p.stem)
def test_gaps_and_busy_fill_the_window(path):
    d = _load(path)
    ops = d["devices"][0]
    idle = sum(e - s for s, e in tr.gaps(ops, d["t0"], d["t1"]))
    assert idle + tr.union_ns(ops) == pytest.approx(d["t1"] - d["t0"], rel=1e-9)
    red = tr.reduce(d, d["t0"], d["t1"], ("data_wait", "host_loop"))
    assert len(red["device_ops"]) <= 10 and len(red["idle_gaps"]) <= 10
    assert all(g[1] > 0 for g in red["idle_gaps"])


@pytest.mark.parametrize("path", TRACES, ids=lambda p: p.stem)
def test_kernels_found_by_name(path):
    d = _load(path)
    workload = path.stem[len("trace_"):]
    for name in KERNELS.get(workload, []):
        assert tr.kernel_events(d["devices"][0], name), name
    assert not tr.kernel_events(d["devices"][0], "no_such_kernel")


def test_containers_left_out():
    assert tr.CONTAINER.match("%while.3")
    assert not tr.CONTAINER.match("%vmap_jit_itp_stdp_update_packed__.8")
    name = "%itp_stdp_conv_delta_packed.14 = f32[128,128]{1,0} custom-call(f32[18432,128] %p)"
    assert tr.op_name(name) == "%itp_stdp_conv_delta_packed.14"


def test_union_of_overlapping_events():
    ev = [("a", 0, 10), ("b", 5, 15), ("c", 20, 30), ("d", 22, 25)]
    assert tr.union_ns(ev) == 25
    assert tr.gaps(ev, 0, 40) == [(15, 20), (30, 40)]
    assert tr.label_gaps([(15, 20)], [("host_loop", 14, 19)], ("host_loop",)) == \
        [["host_loop", 5e-9]]


def test_costs_from_logical_shapes():
    import harness

    c2 = harness.load_json(harness.BENCH / "configs" / "2layer-snn.json")
    (fc,) = costs.layers(c2, 256)
    assert (fc["P"], fc["K"], fc["C"], fc["N_in"]) == (1, 784, 100, 784)
    assert costs.step_flops(c2, 256, True) == 6 * 256 * 784 * 100
    cd = harness.load_json(harness.BENCH / "configs" / "6layer-dcsnn.json")
    c1, c2_, f = costs.layers(cd, 2)
    assert (c1["P"], c1["K"], c1["C"], c1["N_in"]) == (576, 25, 12, 784)
    assert (c2_["P"], c2_["K"], c2_["C"], c2_["N_in"]) == (100, 108, 24, 1728)
    assert (f["P"], f["K"], f["C"]) == (1, 600, 128)
    assert costs.update_bytes(c1) == 2 * 2 * 784 + 2 * 2 * 576 * 12 + 4 * 25 * 12


def _roofline_run(events, rasters):
    import harness

    c = harness.load_json(harness.BENCH / "configs" / "2layer-snn.json")
    traffic = {"batch": 16, "t_steps": 30, "mode": "train"}
    peak = harness.load_json(harness.BENCH / "peaks.json")["TPU v5 lite"]
    win = harness.Window(seconds=1.0, rasters=rasters)
    return {"trace": {"ops": events}, "traffic": traffic, "config": c, "peak": peak,
            "costs": costs, "window": win}


def test_roofline_counts_the_work_not_the_calls():
    from metrics import _roofline

    # two rasters of 30 steps: one event per step, or two per step taking
    # the same time between them, read the same share
    one = [("%itp_stdp_update_packed.1", 1000 * i, 1000 * i + 600) for i in range(60)]
    two = [("%itp_stdp_update_packed.2", 1000 * i + h * 300, 1000 * i + h * 300 + 300)
           for i in range(60) for h in (0, 1)]
    a, bound = _roofline.share(_roofline_run(one, 2), "itp_stdp_update_packed", ("fc",))
    b, _ = _roofline.share(_roofline_run(two, 2), "itp_stdp_update_packed", ("fc",))
    assert a == pytest.approx(b) and bound == "memory"
    (fc,) = costs.layers(_roofline_run(one, 2)["config"], 16)
    t_min, _ = costs.update_min_seconds([fc], _roofline_run(one, 2)["peak"])
    assert a == pytest.approx(100.0 * 60 * t_min / (60 * 600e-9))
    assert _roofline.share(_roofline_run(one, 0), "itp_stdp_update_packed", ("fc",)) is None
    assert _roofline.share(_roofline_run([], 2), "itp_stdp_update_packed", ("fc",)) is None


class _SleepLoop:
    batch = 4

    def __init__(self):
        self.drains = 0

    def step(self, win=None):
        time.sleep(0.01)
        if win is not None:
            win.rasters += 1

    def drain(self):
        self.drains += 1


def test_traced_slice_starts_after_the_lead(tmp_path):
    import harness

    loop = _SleepLoop()
    watch = harness.CompileWatch()
    t = time.perf_counter()
    win = harness.run_window(loop, 0.6, watch, trace_dir=str(tmp_path), trace_lead=0.2,
                             trace_seconds=0.2)
    trace = tr.load(str(tmp_path))
    t0, t1 = tr.host_window(trace["host"], "window")
    assert 0.2 <= win.seconds < 0.3
    assert win.samples == win.rasters * loop.batch and 10 <= win.rasters <= 21
    assert (t1 - t0) / 1e9 == pytest.approx(win.seconds, abs=0.02)
    # drained as the slice begins and ends, and as the window ends
    assert loop.drains == 3
    assert sum(harness.per_second(win.ends_s)) == len(win.ends_s) > win.rasters
    assert time.perf_counter() - t >= 0.6
