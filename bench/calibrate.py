"""Readings behind the limits of a cell's correctness check.

    python3 bench/calibrate.py --workload snn2.train-b256 --seeds 1,2,3 \
        --control-seeds 4,5,6 --fault-seeds 7,8,9

Not a cell and not timed; one process, the cell's own sizes.  For each
seed it prints one JSON line of the numbers ``run.py`` compares:

  * ``sound``: the program as the configuration states it (set-up as in
    a run, then, for inference, ``--batches`` batches at the cell's load);
  * ``control``: the reference at the precision below the stated one
    (forward operands in fp8, update contractions at "high") put in the
    program's place, checked against the reference as stated;
  * ``fault:<name>``: the program with a fault of ``faults.py`` planted.

The lower reading of a number is the largest over the sound seeds, its
upper reading the smallest over the control's and the faults' seeds.
"""
from __future__ import annotations

import argparse
import json

import numpy as np

import compare
import faults
import run


def _ints(s: str) -> list[int]:
    return [int(x) for x in s.split(",") if x]


def sound(workload: str, seed: int, batches: int, **kw) -> dict:
    cell = run.Cell(workload, seed, **kw)
    cell.setup()
    if not cell.train:
        for _ in range(batches):
            cell.loop.step()
        cell.loop.drain()
    cell.release()
    nums, notes = cell.check()
    per = [{k: p[k] for k in ("counts_gap", "row_gap", "layers")}
           for p in getattr(cell, "per_raster", [])]
    return {**nums, "notes": notes, "per_raster": per}


def control(workload: str, seed: int, batches: int, **kw) -> dict:
    """The control reference in the program's place."""
    cell = run.Cell(workload, seed, **kw)
    ref_mod = compare.reference_module(cell.c)
    stated = ref_mod.RefConfig.from_file(cell.c)
    low = ref_mod.RefConfig.from_file(cell.c, forward="fp8", update="high")
    cell.setup()
    if cell.train:
        rasters = [r["spikes"] for r in cell.records]
        init = cell.c["weight_init"]
        w0 = ref_mod.init_weights(cell.k_init, stated, init["low"], init["high"])
        cell.records = compare.reference_records(low, w0, rasters)
        cell.release()
        nums, _ = cell.check(stated)
        return nums
    for _ in range(batches):
        cell.loop.step()
    cell.loop.drain()
    cell.release()
    # the control answers every batch the program answered, from its own draw
    init = cell.c["weight_init"]
    w0 = ref_mod.init_weights(cell.k_init, stated, init["low"], init["high"])
    loop, B, T = cell.loop, cell.traffic["batch"], cell.traffic["t_steps"]
    for i, (k_data, k_enc) in enumerate(loop.keys):
        x, _ = cell.sampler(k_data, B)
        _, counts, _ = ref_mod.run_raster(low, w0, compare.encode(k_enc, x, T),
                                          train=False)
        loop.counts[i] = np.asarray(counts).astype(np.uint8)
    nums, _ = cell.check(stated)
    return nums


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_ints, default=[])
    ap.add_argument("--control-seeds", type=_ints, default=[])
    ap.add_argument("--fault-seeds", type=_ints, default=[])
    ap.add_argument("--faults", default=",".join(faults.FAULTS))
    ap.add_argument("--batches", type=int, default=40,
                    help="inference: batches answered per seed")
    ap.add_argument("--backend", default=None, help="CPU rehearsal only")
    ap.add_argument("--batch", type=int, default=None, help="CPU rehearsal only")
    ap.add_argument("--pool", type=int, default=None, help="CPU rehearsal only")
    args = ap.parse_args()
    kw = {"backend": args.backend, "batch": args.batch, "pool": args.pool}

    def emit(kind: str, seed: int, nums: dict) -> None:
        print(json.dumps({"workload": args.workload, "kind": kind, "seed": seed,
                          **{k: v for k, v in nums.items()}}, default=float), flush=True)

    for s in args.seeds:
        emit("sound", s, sound(args.workload, s, args.batches, **kw))
    for s in args.control_seeds:
        emit("control", s, control(args.workload, s, args.batches, **kw))
    for name in (f for f in args.faults.split(",") if f):
        for s in args.fault_seeds:
            with faults.planted(name):
                emit(f"fault:{name}", s, sound(args.workload, s, args.batches, **kw))


if __name__ == "__main__":
    main()
