"""The ``csnn.train-b256`` cell of ``BENCHMARK.json`` and the reader of the
conv readout's gather, ``window_device_ms``, on the CPU."""
import json
import types

import pytest

import harness
from metrics import _scopes, window_device_ms

WORKLOAD = "csnn.train-b256"
# a run_snn excerpt: a gather under stdp.window, the pack and a history push
# under stdp.timing alone, the update kernel under stdp.update
HLO = """\
  %fusion.7 = u8[2,8]{1,0} fusion(%p0), kind=kLoop, metadata={op_name="jit(run_snn)/while/body/stdp.update/stdp.timing/rev"}
  %fusion.8 = u8[14,8]{1,0} fusion(%fusion.7), kind=kCustom, metadata={op_name="jit(run_snn)/while/body/stdp.update/stdp.timing/stdp.window/gather"}
  %copy.3 = u8[8,14]{1,0} copy(%fusion.8), metadata={op_name="jit(run_snn)/while/body/stdp.update/stdp.timing/stdp.window/reshape;jit(run_snn)/while/body/stdp.timing/stdp.window/reshape"}
  %itp_stdp_conv_delta_packed.2 = f32[128,128]{1,0} custom-call(%copy.3), metadata={op_name="jit(run_snn)/while/body/stdp.update/pallas_call"}
  ROOT %dynamic_update_slice.4 = u8[8,2]{1,0} dynamic-update-slice(%p1), metadata={op_name="jit(run_snn)/while/body/stdp.timing/dynamic_update_slice"}
"""


def _run(ops, rasters=2, traced=True):
    return {"trace": {"ops": ops} if traced else None, "config": {"name": "x"},
            "traffic": {"mode": "train", "batch": 2, "t_steps": 2},
            "window": types.SimpleNamespace(rasters=rasters)}


def test_cell_loads_and_the_program_runs_its_file():
    cell = harness.load_cell(WORKLOAD)
    w, c = cell["workload"], cell["config"]
    assert (w["config"], w["traffic"], w["chips"]) == ("5layer-csnn", "train-b256", 1)
    assert c == harness.load_json(harness.BENCH / "tests" / "data" / "5layer-csnn.json")
    assert cell["traffic"] == {"mode": "train", "batch": 256, "t_steps": 30,
                               "prefetch_depth": 2, "warmup_rasters": 3}
    # program_config stops the run on any setting the maker does not run
    cfg = harness.program_config(c)
    assert cfg.name == "5layer-csnn" and cfg.input_shape == (512, 2)
    assert [(l.kind, l.out_features, l.kernel, l.stride) for l in cfg.layers
            if l.kind != "pool1d"] == [("conv1d", 8, 7, 2), ("conv1d", 16, 5, 2),
                                       ("fc", 64, 3, 1)]


def test_limits_lie_between_their_readings():
    d = harness.load_json(harness.BENCH / "limits" / f"{WORKLOAD}.json")
    lower, upper = d["readings"]["lower"], d["readings"]["upper"]
    assert set(lower) == set(upper) == set(d["limits"]) - {"raster_diff", "init_w_diff"}
    assert d["limits"]["raster_diff"] == d["limits"]["init_w_diff"] == 0.0
    for name in lower:
        assert lower[name] < d["limits"][name] < upper[name][0], name


def test_window_reader_takes_the_union_of_the_scope_ops(monkeypatch):
    monkeypatch.setattr(window_device_ms, "_hlo_text", lambda *a: HLO)
    assert window_device_ms.window_map(HLO) == {"%fusion.8": "stdp.window",
                                                "%copy.3": "stdp.window"}
    ops = [("%fusion.7", 0.0, 1e6), ("%fusion.8", 1e6, 4e6), ("%copy.3", 3e6, 5e6),
           ("%itp_stdp_conv_delta_packed.2", 5e6, 9e6), ("%fusion.8", 10e6, 11e6),
           ("%dynamic_update_slice.4", 11e6, 12e6)]
    # [1, 5] ms and [10, 11] ms over two rasters
    assert window_device_ms.read(_run(ops)) == pytest.approx(2.5)
    # they still count as stdp.timing, the innermost of the three: [0, 5] and [10, 12] ms
    timing = _scopes.device_ms(ops, _scopes.scope_map(HLO), _scopes.TIMING, 2)
    assert timing == pytest.approx(3.5)


def test_window_reader_reads_nothing_without_the_scope(monkeypatch):
    ops = [("%fusion.8", 0.0, 1e6)]
    monkeypatch.setattr(window_device_ms, "_hlo_text", lambda *a: HLO.replace("stdp.window/", ""))
    assert window_device_ms.read(_run(ops)) is None
    monkeypatch.setattr(window_device_ms, "_hlo_text", lambda *a: HLO)
    assert window_device_ms.read(_run([("%fusion.7", 0.0, 1e6)])) is None
    assert window_device_ms.read(_run(ops, traced=False)) is None


@pytest.mark.parametrize("workload,found", [("csnn.train-b256", True),
                                            ("dcsnn.train-b256", True),
                                            ("snn2.train-b16", False)])
def test_compiled_program_holds_the_window_scope_in_conv_cells(workload, found):
    """The text the reader compiles, for the cell's program in interpret mode."""
    c = dict(harness.load_cell(workload)["config"], backend="fused_interpret")
    text = window_device_ms._hlo_text(json.dumps(c, sort_keys=True), True, 2, 2)
    names = window_device_ms.window_map(text)
    assert bool(names) == found
    timing = {n for n, s in _scopes.scope_map(text).items() if s == _scopes.TIMING}
    assert set(names) <= timing
