"""A cell of a 1-D convolutional network, from files alone, on the CPU.

The configuration ``data/5layer-csnn.json`` and the limits
``data/limits_csnn.train-b256.json`` (set from readings on a TPU v5e) are
not entries of ``BENCHMARK.json``: ``harness.load_cell`` is pointed at
them, with the ``train-b256`` traffic, and the run goes through
``run.Cell`` as a cell's does, at a small batch with the kernels in
interpret mode.
"""
import importlib
import re

import jax
import pytest

import calibrate
import faults
import harness
import run
from costs import snn as costs

DATA = harness.BENCH / "tests" / "data"
WORKLOAD = "csnn.train-b256"
SMALL = {"backend": "fused_interpret", "batch": 2, "pool": 200}


def csnn_config() -> dict:
    return harness.load_json(DATA / "5layer-csnn.json")


@pytest.fixture
def cell_from(monkeypatch):
    """Point ``harness.load_cell`` at a conv1d cell of the given config."""
    load_cell = harness.load_cell

    def point(config: dict) -> None:
        def load(workload):
            if workload != WORKLOAD:
                return load_cell(workload)
            return {"workload": {"name": WORKLOAD, "config": config["name"],
                                 "traffic": "train-b256", "chips": 1},
                    "config": config,
                    "traffic": harness.load_json(harness.BENCH / "traffic" / "train-b256.json"),
                    "limits": harness.load_json(DATA / f"limits_{WORKLOAD}.json")["limits"],
                    "spec": {}}

        monkeypatch.setattr(harness, "load_cell", load)

    return point


def _correct(nums: dict) -> bool:
    limits = harness.load_json(DATA / f"limits_{WORKLOAD}.json")["limits"]
    ok, _ = run.verdict({k: v for k, v in nums.items() if k not in ("notes", "per_raster")},
                        limits)
    return ok


def test_setup_window_check_and_step_mfu_run(cell_from):
    cell_from(csnn_config())
    cell = run.Cell(WORKLOAD, 2**40 + 5, **SMALL)
    cell.setup()
    win = cell.window(0.1, None)
    assert win.rasters >= 1 and win.compiles == 0
    cell.release()
    nums, notes = cell.check()
    ok, checks = run.verdict(nums, cell.limits)
    assert ok, checks
    assert set(checks) == set(cell.limits)
    peak = harness.load_json(harness.BENCH / "peaks.json")["TPU v5 lite"]
    got = {"window": win, "traffic": cell.traffic, "config": cell.c, "peak": peak,
           "costs": costs}
    assert importlib.import_module("metrics.step_mfu").read(got) > 0
    # both conv1d layers count towards the conv kernel's roofline
    steps = win.rasters * cell.traffic["t_steps"]
    got["trace"] = {"ops": [("%itp_stdp_conv_delta_packed.3", 0, 10**6 * steps)]}
    conv = [l for l in costs.layers(cell.c, cell.traffic["batch"]) if l["kind"] == "conv1d"]
    assert len(conv) == 2
    t_min, _ = costs.update_min_seconds(conv, peak)
    share = importlib.import_module("metrics.conv_update_roofline").read(got)
    assert share == pytest.approx(100.0 * t_min / 1e-3)


def test_control_is_not_correct(cell_from):
    cell_from(csnn_config())
    assert not _correct(calibrate.control(WORKLOAD, 5, 4, **SMALL))


@pytest.mark.parametrize("fault", faults.FAULTS)
def test_fault_is_not_correct(cell_from, fault):
    cell_from(csnn_config())
    with faults.planted(fault):
        nums = calibrate.sound(WORKLOAD, 7, 4, **SMALL)
    assert not _correct(nums)


@pytest.mark.parametrize("change,message", [
    ({"maker": "no_such_maker"}, "has no network maker 'no_such_maker'"),
    ({"maker": "fmnist_dcsnn"}, "program runs input_shape=(28, 28, 1)"),
    ({"data": {"sampler": "2layer-snn", "generator": "synthetic_digits"}},
     "gives samples of shape (28, 28), file states input_shape (512, 2)"),
])
def test_a_file_that_disagrees_with_the_program_stops(cell_from, change, message):
    cell_from({**csnn_config(), **change})
    with pytest.raises(SystemExit, match=re.escape(message)):
        cell = run.Cell(WORKLOAD, 3, **SMALL)
        cell.setup()


def test_sample_shape_is_checked_with_a_unit_channel_left_out():
    c2 = harness.load_json(harness.BENCH / "configs" / "2layer-snn.json")
    x, _ = harness.make_pool(jax.random.PRNGKey(0), c2, 4)
    assert x.shape == (4, 28, 28) and c2["input_shape"] == [28, 28, 1]
    with pytest.raises(SystemExit, match="gives samples of shape"):
        harness.make_pool(jax.random.PRNGKey(0), {**c2, "input_shape": [28, 27, 1]}, 4)
