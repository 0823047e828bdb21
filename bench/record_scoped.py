"""Record a short scoped excerpt of a cell's trace for the scope readers' tests.

    python3 bench/record_scoped.py --workload snn2.train-b16 --seed 3 --ms 10

Runs the cell's set-up and a short traced window, and writes to
``tests/data/scoped_<workload>.json``: the device ops of the first ``--ms``
milliseconds of the traced slice, the host spans there (the harness's and
the program's own), the rasters whose ``host_loop`` span began there, and
the program scope of every op named in the excerpt (``metrics/_scopes.py``).
"""
from __future__ import annotations

import argparse
import json
import shutil

import harness
import run
import trace_reduce
from metrics import _scopes

# the harness's spans and the measured window, besides the program's own
# spans, which are named "<layer>.<what>" (``repro.tracing``)
KEEP = set(run.HOST_SPANS) | {"window"}
PROGRAM_PREFIXES = ("snn.", "pipeline.")


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
    devices = {d: trace_reduce.clip(ev, t0, t1) for d, ev in trace["devices"].items()}
    host = [h for h in trace_reduce.clip(trace["host"], t0, t1)
            if h[0] in KEEP or h[0].startswith(PROGRAM_PREFIXES)]
    tr = cell.traffic
    scopes = _scopes._compiled_map(json.dumps(cell.c, sort_keys=True), tr["mode"] == "train",
                                   tr["batch"], tr["t_steps"])
    named = {e[0] for ev in devices.values() for e in ev}
    out = {
        "workload": args.workload, "t0": t0, "t1": t1,
        "rasters": sum(1 for n, s, _ in trace["host"] if n == "host_loop" and t0 <= s < t1),
        "devices": devices, "host": host,
        "scopes": {n: s for n, s in scopes.items() if n in named},
    }
    path = harness.BENCH / "tests" / "data" / f"scoped_{args.workload}.json"
    path.parent.mkdir(exist_ok=True)
    with open(path, "w") as f:
        json.dump(out, f)
    print(f"wrote {path}: {sum(len(v) for v in devices.values())} device ops, "
          f"{len(host)} host spans, {len(out['scopes'])} scoped op names, "
          f"{out['rasters']} rasters")


if __name__ == "__main__":
    main()
