"""Benchmark orchestrator: ``PYTHONPATH=src python -m benchmarks.run``.

One module per paper table/figure, registered in
``MODULES`` — the single source the ``--only`` choices, the ``--list``
output, and the dispatch loop all derive from, so the CLI surface cannot
drift from what actually runs (CI smokes ``--list`` against the modules
it exercises):

    drift             — Fig. 5 + §IV-A numbers (RMSE / equilibrium / conv.)
    isi               — Fig. 6 ISI histogram + depth-7 coverage
    network_accuracy  — Table II accuracy parity (3 nets × 3 rules)
    accuracy          — unsupervised train-to-accuracy: ITP vs exact
                        STDP end-to-end (homeostasis + label assignment)
                        across backends, itp-vs-exact gap gated in CI
    engine_cost       — Tables III-V op/bit model + measured SOP/s
    rule_cost         — per-rule engine throughput, reference + fused
                        (ITP vs the fused counter kernels & co.)
    conv_cost         — im2col-fused conv update: reference vs Pallas grid
    sparse_cost       — event-driven sparse backend: speedup vs spike
                        density + sparse/dense crossover
    serve_cost        — online-plasticity serving: step latency,
                        throughput vs batch, bytes/session + sessions/GiB
                        of the packed-word plasticity cache, interleaved
                        bit-identity (gated in CI)
    static_audit      — jaxpr contract audit fingerprint: per-cell
                        primitive counts of the traced rule × backend ×
                        layer-kind matrix (no execution; CI diffs it)

``--only <name>`` runs a single module; ``--quick`` shrinks the
protocols for CI-speed runs; ``--list`` prints the registered module
names (one per line) and exits.  ``summary.json`` is merged
read-modify-write, so successive ``--only`` invocations accumulate their
metrics instead of clobbering each other.
"""
from __future__ import annotations

import argparse
import json
import os
import time


def _run_drift(args):
    from benchmarks import drift
    r = drift.run(args.out)
    return {"rmse": r["metrics"]["update_curve_rmse"]}


def _run_isi(args):
    from benchmarks import isi
    r = isi.run(args.out)
    return {"coverage_at_7": r["pooled_coverage_at_7"]}


def _run_network_accuracy(args):
    from benchmarks import network_accuracy
    kw = {"n_train": 48, "n_test": 32, "seeds": (0,)} if args.quick else {}
    network_accuracy.run(args.out, **kw)
    return {}


def _run_accuracy(args):
    from benchmarks import accuracy
    r = accuracy.run(args.out, quick=args.quick)
    return {"itp_vs_exact_gap": r["itp_vs_exact_gap"],
            "finals": {f"{c['rule']}/{c['backend']}": c["final_accuracy"]
                       for c in r["cells"]}}


def _run_engine_cost(args):
    from benchmarks import engine_cost
    if args.quick:
        r = engine_cost.run(args.out, sizes=(64, 256),
                            grid_sizes=(64, 128, 256), grid_batches=(1, 4),
                            grid_steps=25, quick=True)
    else:
        r = engine_cost.run(args.out)
    return {"speedups": [t["speedup"] for t in r["throughput"]],
            "fused_speedups": [c["fused_speedup"] for c in r["backend_grid"]]}


def _run_rule_cost(args):
    from benchmarks import rule_cost
    if args.quick:
        r = rule_cost.run(args.out, sizes=(64, 128), t_steps=25, quick=True)
    else:
        r = rule_cost.run(args.out)
    return {"itp_vs_exact": [c.get("itp_vs_exact_speedup")
                             for c in r["grid"]],
            "fused_itp_vs_exact": [c.get("fused_itp_vs_exact_speedup")
                                   for c in r["grid"]]}


def _run_conv_cost(args):
    from benchmarks import conv_cost
    r = conv_cost.run(args.out, quick=args.quick)
    return {"fused_speedups": [c["fused_speedup"] for c in r["grid"]]}


def _run_sparse_cost(args):
    from benchmarks import sparse_cost
    if args.quick:
        r = sparse_cost.run(args.out, n=64, t_steps=25,
                            densities=sparse_cost.QUICK_DENSITIES, quick=True)
    else:
        r = sparse_cost.run(args.out)
    return {"model_speedups": [c["model_speedup"] for c in r["grid"]],
            "measured_speedups": [c["measured_speedup"] for c in r["grid"]],
            "crossover_density_model": r["crossover_density_model"]}


def _run_serve_cost(args):
    from benchmarks import serve_cost
    if args.quick:
        r = serve_cost.run(args.out, n_pre=32, n_post=16, t_steps=8,
                           max_batch=4, reps=5,
                           batch_sizes=serve_cost.QUICK_BATCH_SIZES,
                           quick=True)
    else:
        r = serve_cost.run(args.out)
    return {"p50_ms": r["latency"]["p50_ms"],
            "p99_ms": r["latency"]["p99_ms"],
            "bytes_per_neuron": {m["rule"]: m["bytes_per_neuron"]
                                 for m in r["memory"]},
            "interleaved_bit_identical":
                r["isolation"]["interleaved_bit_identical"]}


def _run_static_audit(args):
    from benchmarks import static_audit
    r = static_audit.run(args.out, quick=args.quick)
    return {"n_cells": r["n_cells"], "n_violating": r["n_violating"]}


# name → runner; insertion order is execution order.  --only choices,
# --list, and the dispatch loop below all read THIS dict — add a module
# here and every CLI surface picks it up.
MODULES = {
    "drift": _run_drift,
    "isi": _run_isi,
    "network_accuracy": _run_network_accuracy,
    "accuracy": _run_accuracy,
    "engine_cost": _run_engine_cost,
    "rule_cost": _run_rule_cost,
    "conv_cost": _run_conv_cost,
    "sparse_cost": _run_sparse_cost,
    "serve_cost": _run_serve_cost,
    "static_audit": _run_static_audit,
}


def _merge_summary(path: str, update: dict) -> dict:
    """Read-modify-write summary.json so --only runs accumulate."""
    summary = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                summary = json.load(f)
        except (json.JSONDecodeError, OSError):
            summary = {}
    summary.update(update)
    with open(path, "w") as f:
        json.dump(summary, f, indent=1)
    return summary


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", choices=tuple(MODULES))
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--list", action="store_true",
                    help="print the registered benchmark modules and exit")
    ap.add_argument("--out", default="experiments/bench")
    args = ap.parse_args()

    if args.list:
        for name in MODULES:
            print(name)
        return

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    os.makedirs(args.out, exist_ok=True)
    results = {}
    t_start = time.time()
    for name, runner in MODULES.items():
        if args.only is not None and args.only != name:
            continue
        t0 = time.time()
        metrics = runner(args)
        results[name] = {"seconds": round(time.time() - t0, 1), **metrics}
        print()

    results["total_seconds"] = round(time.time() - t_start, 1)
    _merge_summary(os.path.join(args.out, "summary.json"), results)
    print(f"benchmarks complete in {results['total_seconds']}s "
          f"→ {args.out}/")


if __name__ == "__main__":
    main()
