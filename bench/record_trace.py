"""Record a short excerpt of a cell's trace for the reduction's tests.

    python3 bench/record_trace.py --workload snn2.train-b16 --seed 3 --ms 10

Runs the cell's set-up and a short traced window, and writes the device
ops and the harness's host spans of the first ``--ms`` milliseconds of
the traced slice to ``tests/data/trace_<workload>.json``.
"""
from __future__ import annotations

import argparse
import json
import shutil

import harness
import run
import trace_reduce


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--ms", type=float, default=10.0)
    args = ap.parse_args()
    run.device_info(1)
    cell = run.Cell(args.workload, args.seed)
    cell.setup()
    trace_dir = str(harness.BENCH / ".trace" / f"record_{args.workload}")
    shutil.rmtree(trace_dir, ignore_errors=True)
    cell.window(run.TRACE_LEAD_SECONDS + 1.0, trace_dir)
    cell.release()
    trace = trace_reduce.load(trace_dir)
    shutil.rmtree(trace_dir, ignore_errors=True)
    t0, _ = trace_reduce.host_window(trace["host"], "window")
    t1 = t0 + args.ms * 1e6
    keep = set(run.HOST_SPANS)
    out = {
        "workload": args.workload, "t0": t0, "t1": t1,
        "devices": {d: trace_reduce.clip(ev, t0, t1) for d, ev in trace["devices"].items()},
        "host": [h for h in trace_reduce.clip(trace["host"], t0, t1) if h[0] in keep],
    }
    path = harness.BENCH / "tests" / "data" / f"trace_{args.workload}.json"
    path.parent.mkdir(exist_ok=True)
    with open(path, "w") as f:
        json.dump(out, f)
    print(f"wrote {path}: {sum(len(v) for v in out['devices'].values())} device ops, "
          f"{len(out['host'])} host spans")


if __name__ == "__main__":
    main()
