"""Benchmark: event-driven sparse backend — speedup vs spike density.

The repo-side analogue of the paper's event-driven-efficiency argument:
the same engine (identical LIF dynamics, scan loop, and jit) runs the
dense ``reference`` weight-update datapath against the event-driven
``sparse`` datapath over a grid of input spike densities.  The dense
update always touches all n² synapses; the sparse update extracts
static-shape event lists (capped at ``max_events``, scaled to the
density with 2× headroom so drops stay rare) and scatters only the
touched rows/columns — so its cost scales with events, not synapses,
and there is a *crossover density* below which sparse wins.

Two speedup columns per density cell:

  * ``model_speedup``   — the host-independent event-cost model: dense
    touches n² cells per step; sparse touches n·(e_pre + e_post) cells
    plus O(n) per side for the event extraction, with e the static
    event-list cap actually in effect.  This is the structural claim and
    is what CI gates unconditionally.
  * ``measured_speedup`` — jitted engine-scan wall-clock (SOP/s ratio).
    Gated only where it is meaningful — on a compiled fused backend host
    (``gate_measured``) — because small-n CPU wall-clock is dominated by
    dispatch overhead, not the datapath (same caveat as the rule-cost
    grid, see ROADMAP).

The crossover densities (modelled and measured, linear interpolation of
the speedup-vs-density curve through 1.0) land in the JSON next to the
roofline arithmetic-intensity ridge of one v5e chip (``PEAK_FLOPS /
HBM_BW``) — the target the sparse datapath's gather/scatter traffic is
priced against.

Merges a ``sparse`` section into the tracked repo-root BENCH_engine.json
(``benchmarks/bench_io.py`` read-modify-write, never clobbering the
engine/rules/conv sections); ``--quick`` runs use the smaller,
incomparable grid and land in the gitignored ``.quick`` twin.
"""

from __future__ import annotations

import json
import math
import os

import jax

from benchmarks.bench_io import update_bench_json
from benchmarks.rule_cost import _time_fn
from repro.core.engine import EngineConfig, init_engine, run_engine
from repro.kernels.dispatch import default_fused_backend
from repro.kernels.itp_sparse.events import event_cap

PEAK_FLOPS = 197e12  # bf16 / chip (TPU v5e)
HBM_BW = 819e9  # B/s / chip (TPU v5e)

DENSITIES = (0.01, 0.02, 0.05, 0.1, 0.2, 0.4)
QUICK_DENSITIES = (0.02, 0.2)

# event-list cap headroom over the expected per-step event count: 2× the
# Bernoulli mean keeps cap-overflow drops rare while keeping the static
# gather/scatter shapes proportional to the density
CAP_HEADROOM = 2.0


def density_cap(n: int, density: float) -> int:
    """The static event-list cap the sparse backend runs with at ``density``."""
    return event_cap(n, max(1, math.ceil(CAP_HEADROOM * density * n)))


def model_costs(n: int, density: float) -> tuple[float, float]:
    """(dense, sparse) modelled cells-touched per engine step.

    Dense: the full n² synapse matrix.  Sparse: n·e per side (the LTP
    scatter touches n rows × e_post columns, the LTD scatter e_pre rows
    × n columns) plus an O(n) event extraction per side, with e the
    static cap in effect at this density.
    """
    e = density_cap(n, density)
    return float(n * n), float(2 * n * e + 2 * n)


def measure_density_throughput(
    n: int, t_steps: int, density: float, backend: str, seed: int = 0
) -> float:
    """SOP/s of a jitted engine scan at ``density`` on ``backend``."""
    key = jax.random.PRNGKey(seed)
    max_events = density_cap(n, density) if backend == "sparse" else None
    cfg = EngineConfig(n_pre=n, n_post=n, backend=backend, max_events=max_events)
    state = init_engine(key, cfg)
    train = jax.random.bernoulli(key, density, (t_steps, n))
    fn = jax.jit(lambda s, x: run_engine(s, x, cfg))
    return n * n * t_steps / _time_fn(fn, state, train)


def crossover_density(rows: list[dict], key: str) -> float | None:
    """Density where the ``key`` speedup curve crosses 1.0 (sparse = dense).

    Linear interpolation between adjacent grid points; the lowest-density
    crossing wins.  None when the curve never crosses (all-above means
    sparse wins everywhere benchmarked; all-below, nowhere).
    """
    pts = [(r["density"], r[key]) for r in rows if r.get(key) is not None]
    for (d0, s0), (d1, s1) in zip(pts, pts[1:]):
        if (s0 - 1.0) * (s1 - 1.0) <= 0.0 and s0 != s1:
            return d0 + (d1 - d0) * (1.0 - s0) / (s1 - s0)
    return None


def measure_density_grid(n: int, t_steps: int, densities) -> list[dict]:
    """Sparse-vs-dense engine throughput + event-cost model per density."""
    rows = []
    for density in densities:
        dense_cost, sparse_cost = model_costs(n, density)
        dense = measure_density_throughput(n, t_steps, density, "reference")
        sparse = measure_density_throughput(n, t_steps, density, "sparse")
        rows.append(
            {
                "density": density,
                "n": n,
                "t_steps": t_steps,
                "max_events": density_cap(n, density),
                "dense_sops_per_s": dense,
                "sparse_sops_per_s": sparse,
                "measured_speedup": sparse / dense,
                "model_dense_cost": dense_cost,
                "model_sparse_cost": sparse_cost,
                "model_speedup": dense_cost / sparse_cost,
            }
        )
    return rows


def run(
    out_dir: str = "experiments/bench",
    verbose: bool = True,
    n: int = 256,
    t_steps: int = 50,
    densities=DENSITIES,
    quick: bool = False,
) -> dict:
    grid = measure_density_grid(n, t_steps, densities)
    out = {
        "benchmark": "sparse_vs_dense_engine_throughput",
        "unit": "SOP/s",
        "quick": quick,
        "gate_measured": default_fused_backend() == "fused",
        "n": n,
        "t_steps": t_steps,
        "grid": grid,
        "crossover_density_model": crossover_density(grid, "model_speedup"),
        "crossover_density_measured": crossover_density(grid, "measured_speedup"),
        "ai_ridge_flops_per_byte": PEAK_FLOPS / HBM_BW,
        "note": "event-cost model gated always; wall-clock only on compiled hosts",
    }
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "sparse_cost.json"), "w") as f:
        json.dump(out, f)
    bench_name = "BENCH_engine.quick.json" if quick else "BENCH_engine.json"
    update_bench_json(bench_name, {"sparse": out})
    if verbose:
        print(f"— sparse vs dense engine throughput (n={n}, {t_steps} steps) —")
        hdr = (
            f"  {'density':>8s} {'cap':>5s} {'dense':>12s} {'sparse':>12s}"
            f" {'measured×':>10s} {'model×':>8s}"
        )
        print(hdr)
        for r in grid:
            print(
                f"  {r['density']:8.3f} {r['max_events']:5d}"
                f" {r['dense_sops_per_s']:12.3e} {r['sparse_sops_per_s']:12.3e}"
                f" {r['measured_speedup']:10.2f} {r['model_speedup']:8.2f}"
            )
        xm, xw = out["crossover_density_model"], out["crossover_density_measured"]
        print(
            f"  crossover density: model "
            f"{'—' if xm is None else format(xm, '.3f')}, measured "
            f"{'—' if xw is None else format(xw, '.3f')}"
            f" (AI ridge {out['ai_ridge_flops_per_byte']:.0f} FLOP/byte)"
        )
        print(f"  → {bench_name} (sparse section, {len(grid)} densities)")
    return out


if __name__ == "__main__":
    run()
