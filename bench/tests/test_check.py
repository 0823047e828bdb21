"""The correctness check drives the timed path and catches what it must.

Each case runs a cell's set-up (and, for inference, some batches) at a
small batch on the CPU with the kernels in interpret mode, then the
check with the cell's own limits: sound runs pass; the control (the
reference at the precision below the stated one, in the program's
place) and every fault the cell can have fail.
"""
import pytest

import calibrate
import faults
import run

SMALL = {"backend": "fused_interpret", "batch": 4, "pool": 200}
CELLS = {
    "snn2.train-b16": SMALL,
    "snn2.infer-b256": SMALL,
    "dcsnn.train-b256": {**SMALL, "batch": 2},
}
FAULTS = [(w, f) for w in CELLS for f in faults.FAULTS
          if not (f == "unchanged" and w.endswith("infer-b256"))]


def _correct(workload: str, nums: dict) -> bool:
    cell = run.Cell(workload, 0, **CELLS[workload])
    ok, _ = run.verdict({k: v for k, v in nums.items()
                         if k not in ("notes", "per_raster")}, cell.limits)
    return ok


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_sound_run_is_correct(workload):
    assert _correct(workload, calibrate.sound(workload, 2**40 + 3, 4, **CELLS[workload]))


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_control_is_not_correct(workload):
    assert not _correct(workload, calibrate.control(workload, 5, 4, **CELLS[workload]))


@pytest.mark.parametrize("workload,fault", FAULTS)
def test_fault_is_not_correct(workload, fault):
    with faults.planted(fault):
        nums = calibrate.sound(workload, 7, 4, **CELLS[workload])
    assert not _correct(workload, nums)


def test_inference_weights_not_drawn_from_the_seed_are_not_correct(monkeypatch):
    """The inference check draws its own weights: a program whose initial
    weights are off fails it, though its answers follow its own weights."""
    from repro.models import snn

    init_snn = snn.init_snn

    def off(key, cfg, batch):
        state = init_snn(key, cfg, batch)
        return state._replace(weights=tuple(w * 0.999 for w in state.weights))

    monkeypatch.setattr(snn, "init_snn", off)
    nums = calibrate.sound("snn2.infer-b256", 11, 4, **CELLS["snn2.infer-b256"])
    assert nums["init_w_diff"] > 0 and nums["frozen_w_diff"] > 0
    assert not _correct("snn2.infer-b256", nums)
