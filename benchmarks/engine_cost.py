"""Benchmark: Tables III-V analogue — per-update operation/storage model +
measured learning-engine throughput.

Silicon metrics (GHz, µm², pJ/SOP) do not transfer to a JAX repro
(docs/architecture.md §7); what does transfer is the *operation-count asymmetry* the
tables monetise.  Two parts:

1. **Op/bit-count model** — arithmetic ops + storage bits per synaptic
   weight update for each STDP implementation family.  Reproduces the
   paper's structural claim: ITP-STDP needs no exponential, no multiplier,
   no LUT — only register reads, shifts, adds.

2. **Measured throughput (SOP/s)** — the ITP engine vs the conventional
   counter-based exact-STDP engine (identical LIF dynamics, identical
   pairing) at several sizes, both jit-compiled.  CPU wall-time stands in
   for the hardware's cycle count; the *ratio* is the algorithmic win.
"""
from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp

from benchmarks.bench_io import update_bench_json
from repro.core.engine import (EngineConfig, init_engine,
                               init_engine_population, run_engine,
                               run_engine_population)
from repro.kernels.dispatch import default_fused_backend, resolve_backend

# ---------------------------------------------------------------------------
# 1. Op/bit-count model (per synaptic weight update, nearest-neighbour)
# ---------------------------------------------------------------------------
# Conventions: depth-7 history, 8-bit weights (the paper's datapath).
# 'exp' = base-e exponential evaluation; 'approx_mul' = Mitchell-style
# shift-add multiply (3 per LLSMu); 'lut_bits' = precomputed-table storage.

D = 7          # history depth
WB = 8         # weight bits

OP_MODEL = {
    # counter Δt + exp + A·(.) multiply + accumulate      [26]/[28]-style
    "P-STDP (exact)": {
        "exp": 1, "mul": 1, "approx_mul": 0, "sub": 1, "shift": 0,
        "add": 1, "lut_bits": 0,
        "state_bits_per_neuron": 2 * 8,            # 2 saturating counters
    },
    # PWL approximation [24]: slope multiply + clip
    "P-STDP (linear [24])": {
        "exp": 0, "mul": 1, "approx_mul": 0, "sub": 2, "shift": 0,
        "add": 1, "lut_bits": 0,
        "state_bits_per_neuron": 2 * 8,
    },
    # trace-based with LLSMu approximate multiplier [29]
    "t-STDP (LLMu [29])": {
        "exp": 0, "mul": 0, "approx_mul": 1, "sub": 1, "shift": 2,
        "add": 2, "lut_bits": 0,
        "state_bits_per_neuron": 2 * WB,           # pre/post traces
    },
    # index-difference + precomputed LUT [23]
    "ImSTDP [23]": {
        "exp": 0, "mul": 0, "approx_mul": 0, "sub": 1, "shift": 0,
        "add": 1, "lut_bits": 2 * D * WB,          # LTP+LTD tables
        "state_bits_per_neuron": 2 * 8,            # spike indices
    },
    # this work: register read IS the update
    "ITP-STDP (this work)": {
        "exp": 0, "mul": 0, "approx_mul": 0, "sub": 0, "shift": 1,
        "add": 1, "lut_bits": 0,
        "state_bits_per_neuron": D,                # the shift register
    },
    # reward-modulated ITP (rule="mstdp"): the same register read scaled
    # by a per-neuron eligibility word — shift decay + credit add on the
    # word, one multiply for the /128 fixed-point modulation (reward
    # folds into the same scale)
    "R-STDP (mstdp, this work)": {
        "exp": 0, "mul": 1, "approx_mul": 0, "sub": 0, "shift": 2,
        "add": 2, "lut_bits": 0,
        "state_bits_per_neuron": D + 8,            # registers + eligibility
    },
}


# ---------------------------------------------------------------------------
# 2. Measured throughput
# ---------------------------------------------------------------------------

def _time_fn(fn, *args, reps: int = 3) -> float:
    fn(*args)                       # compile + warm
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    return best


def measure_throughput(n: int, t_steps: int = 100, seed: int = 0) -> dict:
    """ITP engine vs the counter-based exact-STDP rule, unified engine API.

    ``rule="exact"`` is the old standalone CounterEngine folded into the
    learning-rule registry (per-pair Δt + base-e exponential); identical
    LIF dynamics and scan loop, so the ratio isolates the update datapath.
    """
    key = jax.random.PRNGKey(seed)
    train = jax.random.bernoulli(key, 0.3, (t_steps, n))

    itp_cfg = EngineConfig(n_pre=n, n_post=n)
    itp_state = init_engine(key, itp_cfg)
    itp = jax.jit(lambda s, x: run_engine(s, x, itp_cfg))
    t_itp = _time_fn(itp, itp_state, train)

    cnt_cfg = EngineConfig(n_pre=n, n_post=n, rule="exact")
    cnt_state = init_engine(key, cnt_cfg)
    cnt = jax.jit(lambda s, x: run_engine(s, x, cnt_cfg))
    t_cnt = _time_fn(cnt, cnt_state, train)

    sops = n * n * t_steps
    return {"n": n, "t_steps": t_steps,
            "itp_sops_per_s": sops / t_itp,
            "counter_sops_per_s": sops / t_cnt,
            "speedup": t_cnt / t_itp}


def measure_backend_throughput(n: int, replicas: int, t_steps: int,
                               backend: str, seed: int = 0) -> float:
    """SOP/s of the population engine on one weight-update backend."""
    key = jax.random.PRNGKey(seed)
    cfg = EngineConfig(n_pre=n, n_post=n, backend=backend)
    states = init_engine_population(key, cfg, replicas)
    trains = jax.random.bernoulli(jax.random.fold_in(key, 1), 0.3,
                                  (replicas, t_steps, n))
    fn = jax.jit(lambda s, x: run_engine_population(s, x, cfg))
    t = _time_fn(fn, states, trains)
    return replicas * t_steps * n * n / t


def fused_backend_name() -> str:
    """The fused backend this host can actually run.

    Delegates to ``repro.kernels.dispatch.default_fused_backend`` (CPU can
    only run the Pallas kernels through the interpreter; on an accelerator
    the real compiled kernel is measured).  The chosen name is recorded in
    the artifact so interpreter numbers are never mistaken for kernel
    numbers.
    """
    return default_fused_backend()


# ---------------------------------------------------------------------------
# 3. Packed vs unpacked history datapath (HBM bytes + throughput)
# ---------------------------------------------------------------------------

def measure_packed_history(n: int, depth: int = 7, t_steps: int = 50,
                           seed: int = 0) -> dict:
    """Packed uint8 words vs unpacked float32 bitplanes into the fused kernel.

    Times a jitted ``t_steps`` scan of the fused weight update fed by (a)
    depth-major ``(depth, n)`` float32 bitplane registers and (b) one packed
    uint8 word per neuron, and records the per-step history bytes each
    variant moves into the kernel — the ~``4·depth``× traffic reduction the
    paper's 8-bit register file realises (ROADMAP bandwidth item).
    """
    from repro.core.history import pack_bitplanes
    from repro.core.stdp import STDPParams
    from repro.kernels.itp_stdp.ops import (weight_update_depth_major,
                                            weight_update_packed)

    _, interpret = resolve_backend(fused_backend_name())
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    pre_bits = jax.random.bernoulli(ks[0], 0.3, (t_steps, depth, n))
    post_bits = jax.random.bernoulli(ks[1], 0.3, (t_steps, depth, n))
    pre_s = jax.random.bernoulli(ks[2], 0.35, (t_steps, n))
    post_s = jax.random.bernoulli(ks[3], 0.35, (t_steps, n))
    # (t, n) uint8 words via the canonical packer (depth axis first)
    pre_words = jax.vmap(pack_bitplanes)(pre_bits)
    post_words = jax.vmap(pack_bitplanes)(post_bits)
    params = STDPParams()
    eta = 1.0 / 16.0

    def scan_unpacked(w):
        def step(w, xs):
            p, q, pb, qb = xs
            return weight_update_depth_major(
                w, p, q, pb, qb, params, eta=eta, interpret=interpret), None
        out, _ = jax.lax.scan(step, w, (pre_s, post_s, pre_bits, post_bits))
        return out

    def scan_packed(w):
        def step(w, xs):
            p, q, pw, qw = xs
            return weight_update_packed(
                w, p, q, pw, qw, params, depth=depth, eta=eta,
                interpret=interpret), None
        out, _ = jax.lax.scan(step, w, (pre_s, post_s, pre_words, post_words))
        return out

    w0 = jnp.full((n, n), 0.5, jnp.float32)
    t_unpacked = _time_fn(jax.jit(scan_unpacked), w0)
    t_packed = _time_fn(jax.jit(scan_packed), w0)
    sops = n * n * t_steps
    return {
        "n": n, "depth": depth, "t_steps": t_steps,
        # per-step history operand bytes entering the kernel (pre + post)
        "unpacked_history_bytes_per_step": 2 * depth * n * 4,
        "packed_history_bytes_per_step": 2 * n * 1,
        "history_bytes_reduction": float(4 * depth),
        "unpacked_sops_per_s": sops / t_unpacked,
        "packed_sops_per_s": sops / t_packed,
        "packed_speedup": t_unpacked / t_packed,
    }


def measure_backend_grid(sizes=(128, 256, 512), batches=(1, 8),
                         t_steps: int = 50) -> list[dict]:
    """Reference-vs-fused throughput over a (batch × engine-size) grid."""
    fused_name = fused_backend_name()
    rows = []
    for n in sizes:
        for r in batches:
            ref = measure_backend_throughput(n, r, t_steps, "reference")
            fused = measure_backend_throughput(n, r, t_steps, fused_name)
            rows.append({"n": n, "replicas": r, "t_steps": t_steps,
                         "fused_backend": fused_name,
                         "reference_sops_per_s": ref,
                         "fused_sops_per_s": fused,
                         "fused_speedup": fused / ref})
    return rows


def run(out_dir: str = "experiments/bench", verbose: bool = True,
        sizes=(256, 512, 1024), grid_sizes=(128, 256, 512),
        grid_batches=(1, 8), grid_steps: int = 50,
        quick: bool = False) -> dict:
    throughput = [measure_throughput(n) for n in sizes]
    backend_grid = measure_backend_grid(grid_sizes, grid_batches, grid_steps)
    packed_grid = [measure_packed_history(n, t_steps=grid_steps)
                   for n in grid_sizes]
    out = {"op_model": OP_MODEL, "throughput": throughput,
           "backend_grid": backend_grid,
           "packed_grid": packed_grid,
           "paper_claims": {
               "fpga_energy_eff_gain": "4.5x-219.8x",
               "asic_speedup": "4.8x-22.01x",
               "asic_area_fraction": "1.2%-3.3%",
           }}
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "engine_cost.json"), "w") as f:
        json.dump(out, f)
    # repo-root perf trajectory artifact: reference vs fused engine
    # throughput per (size, batch) cell — the first point every later
    # scaling PR appends to.  --quick runs use a smaller, incomparable
    # grid, so they write a separate (gitignored) file rather than
    # clobbering the tracked trajectory.  Merged, not overwritten: the
    # conv grid (benchmarks/conv_cost.py) shares the same file.
    bench_name = "BENCH_engine.quick.json" if quick else "BENCH_engine.json"
    update_bench_json(bench_name,
                      {"benchmark": "engine_backend_throughput",
                       "unit": "SOP/s",
                       "quick": quick,
                       "fused_backend": fused_backend_name(),
                       "grid": backend_grid,
                       # packed uint8 words vs unpacked f32 bitplanes into
                       # the fused kernel: HBM history bytes + throughput
                       "packed": {
                           "benchmark": "packed_history_datapath",
                           "unit": "SOP/s",
                           "quick": quick,
                           "fused_backend": fused_backend_name(),
                           "grid": packed_grid,
                       }})
    if verbose:
        print("— engine cost model (paper Tables III-V analogue) —")
        hdr = f"  {'variant':24s} {'exp':>4s} {'mul':>4s} {'amul':>5s} " \
              f"{'sub':>4s} {'shift':>6s} {'add':>4s} {'LUTb':>5s} " \
              f"{'state-b/neuron':>15s}"
        print(hdr)
        for name, m in OP_MODEL.items():
            print(f"  {name:24s} {m['exp']:4d} {m['mul']:4d} "
                  f"{m['approx_mul']:5d} {m['sub']:4d} {m['shift']:6d} "
                  f"{m['add']:4d} {m['lut_bits']:5d} "
                  f"{m['state_bits_per_neuron']:15d}")
        print("  measured engine throughput (jit, CPU timing, relative):")
        for t in throughput:
            print(f"    n={t['n']:5d}: ITP {t['itp_sops_per_s']:.3e} SOP/s  "
                  f"counter-exact {t['counter_sops_per_s']:.3e} SOP/s  "
                  f"speedup ×{t['speedup']:.2f}")
        print("  backend grid (reference vs fused Pallas datapath):")
        for row in backend_grid:
            print(f"    n={row['n']:5d} R={row['replicas']:3d}: "
                  f"ref {row['reference_sops_per_s']:.3e} SOP/s  "
                  f"fused {row['fused_sops_per_s']:.3e} SOP/s  "
                  f"×{row['fused_speedup']:.2f}")
        print("  packed history datapath (uint8 words vs f32 bitplanes):")
        for row in packed_grid:
            print(f"    n={row['n']:5d} d={row['depth']}: "
                  f"{row['unpacked_history_bytes_per_step']:7d} B/step → "
                  f"{row['packed_history_bytes_per_step']:5d} B/step "
                  f"(÷{row['history_bytes_reduction']:.0f})  "
                  f"packed {row['packed_sops_per_s']:.3e} SOP/s  "
                  f"×{row['packed_speedup']:.2f}")
        print(f"  → {bench_name} ({len(backend_grid)} grid cells)")
    return out


if __name__ == "__main__":
    run()
