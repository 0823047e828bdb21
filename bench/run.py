"""One run of one benchmark cell on the chip.

    python3 bench/run.py --workload snn2.train-b256 --seed 7 --seconds 10 --trace 0

Set-up: the device-resident data pool and the initial weights from the
seed, then the cell's first rasters through the window's own loop object
(they compile every program the window uses, and are the rasters the
correctness check compares).  Then ``--seconds`` of the measured window,
with nothing compiled inside it.  Then the check against the plain
reference, outside all timing.

``--trace 0`` prints the cell's end-to-end metrics; ``--trace 1`` traces
the window with the JAX profiler and prints the per-layer metrics.  The
last line of stdout is one JSON object; the numbers compared, each with
its limit, are the last lines of stderr and the last key of that object.
A run that finds no TPU, or fewer chips than the cell needs, exits with
an error and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

import harness  # noqa: E402
import jax  # noqa: E402
import numpy as np  # noqa: E402

import compare  # noqa: E402
import trace_reduce  # noqa: E402
from costs import snn as costs  # noqa: E402

HOST_SPANS = ("data_wait", "host_loop", "result_wait")
# the traced slice of a --trace 1 run's window: it begins a second in, so
# that the profiler's start-up stays out of it, and is long enough for
# hundreds of rasters of the fast cells, short enough that the trace stays
# small
TRACE_LEAD_SECONDS = 1.0
TRACE_SECONDS = 3.0


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def device_info(chips: int) -> dict:
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        raise SystemExit(f"bench: no TPU found (first device: {dev.platform})")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, found {len(devs)}")
    return {"platform": dev.platform, "kind": dev.device_kind, "count": len(devs)}


def compile_cache() -> None:
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    # every program of a cell, however quick to compile, is read back
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


class Cell:
    """Set-up, window and check of one cell; ``backend`` and ``pool`` are
    for the CPU tests, which drive the same path in interpret mode."""

    def __init__(self, workload: str, seed: int, *, backend: str | None = None,
                 pool: int | None = None, batch: int | None = None):
        cell = harness.load_cell(workload)
        self.c = cell["config"]
        self.traffic = dict(cell["traffic"])
        if batch:
            self.traffic["batch"] = batch
        self.limits = cell["limits"]
        self.train = self.traffic["mode"] == "train"
        self.seed = seed
        override = {"backend": backend} if backend else {}
        self.cfg = harness.program_config(self.c, **override)
        key = harness.seed_key(seed)
        self.k_pool, self.k_stream, self.k_init = (
            jax.random.fold_in(key, i)
            for i in (harness.POOL_KEY, harness.STREAM_KEY, harness.INIT_KEY))
        self.pool_n = pool or self.c["assumed"]["pool_samples"]
        self.watch = harness.CompileWatch()
        self.records: list = []

    def setup(self) -> None:
        B = self.traffic["batch"]
        with harness.forward_precision(self.c):
            self.pool = harness.make_pool(self.k_pool, self.c, self.pool_n)
            self.sampler = harness.pool_sampler(self.pool)
            state = harness.snn.init_snn(self.k_init, self.cfg, B)
            self.w0 = state.weights
            if self.train:
                self.loop = harness.Trainer(self.cfg, state, self.k_stream,
                                            self.sampler, self.traffic)
                for _ in range(self.traffic["warmup_rasters"]):
                    self.loop.step(record=self.records)
            else:
                self.loop = harness.Classifier(self.cfg, state, self.k_stream,
                                               self.sampler, self.traffic)
                for _ in range(self.traffic["warmup_rasters"]):
                    self.loop.step()
            self.loop.drain()

    def window(self, seconds: float, trace_dir: str | None):
        with harness.forward_precision(self.c):
            return harness.run_window(self.loop, seconds, self.watch, trace_dir=trace_dir,
                                      trace_lead=TRACE_LEAD_SECONDS,
                                      trace_seconds=TRACE_SECONDS)

    def release(self) -> None:
        """Stop the loop and free the program's state before the check."""
        self.loop.close()
        self.final_w = [np.asarray(w) for w in self.loop.state.weights]
        self.loop.state = None
        gc.collect()

    # -- the check -----------------------------------------------------

    def check(self, rc=None) -> tuple[dict, list]:
        """Numbers compared with the reference, and lines of post rates."""
        ref_mod = compare.reference_module(self.c)
        rc = rc or ref_mod.RefConfig.from_file(self.c)
        if self.train:
            nums, per = compare.training_numbers(
                self.c, rc, self.records, self.k_stream, self.sampler, self.k_init)
            self.records = []
            self.per_raster = per
            notes = [self._rates_line(per)]
            return nums, notes
        return self._check_inference(rc, ref_mod)

    def _check_inference(self, rc, ref_mod) -> tuple[dict, list]:
        """A sample of the window's batches, answered by the reference from
        weights it draws itself from the seed and its own encoding."""
        loop = self.loop
        n = len(loop.keys)
        rng = np.random.default_rng(self.seed)
        take = sorted(rng.choice(n, size=min(n, self.traffic["checked_batches"]),
                                 replace=False))
        init = self.c["weight_init"]
        w_ref = ref_mod.init_weights(self.k_init, rc, init["low"], init["high"])
        mine_all, theirs_all = [], []
        rates = []
        for i in take:
            k_data, k_enc = loop.keys[i]
            x, _ = self.sampler(k_data, self.traffic["batch"])
            mine = compare.encode(k_enc, x, self.traffic["t_steps"])
            _, counts, layers = ref_mod.run_raster(rc, w_ref, mine, train=False)
            theirs_all.append(np.asarray(counts))
            mine_all.append(loop.counts[i])
            rates.append([float(np.mean(np.asarray(l["spikes"]))) / self.traffic["t_steps"]
                          for l in layers])
        counts_gap, row_gap = compare.count_gaps(np.concatenate(mine_all),
                                                 np.concatenate(theirs_all))
        nums = {"init_w_diff": compare.max_abs_diff(self.w0, w_ref),
                "counts_gap": counts_gap, "row_gap": row_gap,
                "frozen_w_diff": compare.max_abs_diff(self.final_w, w_ref)}
        mean_rates = np.mean(np.asarray(rates), axis=0).tolist() if rates else []
        notes = [f"checked batches: {len(take)} of {n}",
                 f"mean post rate per learnable layer: {mean_rates}"]
        return nums, notes

    def _rates_line(self, per: list) -> str:
        rates = np.mean([p["post_rates"] for p in per], axis=0).tolist()
        up = [int(np.sum(np.asarray(b) > np.asarray(a)))
              for a, b in zip(self.w0, self.final_w)]
        down = [int(np.sum(np.asarray(b) < np.asarray(a)))
                for a, b in zip(self.w0, self.final_w)]
        return (f"mean post rate per learnable layer (checked rasters): {rates}; "
                f"weights up/down over the run per layer: {up} / {down}")


def verdict(nums: dict, limits: dict) -> tuple[bool, dict]:
    checks = {}
    for name, value in nums.items():
        if name not in limits:
            raise SystemExit(f"no limit for compared number {name!r}")
        checks[name] = {"value": value, "limit": limits[name]}
    ok = all(v["value"] <= v["limit"] for v in checks.values())
    return ok, checks


def metric_names(spec: dict, workload: str, kind: str) -> list[dict]:
    return [m for m in spec[kind] if workload in m.get("workloads", [workload])]


def main(argv=None) -> int:
    args = parse(argv)
    cell_spec = harness.load_cell(args.workload)
    info = device_info(cell_spec["workload"]["chips"])
    compile_cache()
    cell = Cell(args.workload, args.seed)
    cell.setup()
    setup_s = time.perf_counter() - T_START
    trace_dir = None
    if args.trace:
        trace_dir = str(harness.BENCH / ".trace" / args.workload)
        shutil.rmtree(trace_dir, ignore_errors=True)
    win = cell.window(args.seconds, trace_dir)
    if win.compiles:
        raise SystemExit(f"bench: {win.compiles} compilations inside the window")
    stats = jax.devices()[0].memory_stats() or {}
    info["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))
    cell.release()

    out: dict = {}
    spec = cell_spec["spec"]
    if args.trace:
        trace = trace_reduce.load(trace_dir)
        t0, t1 = trace_reduce.host_window(trace["host"], "window")
        red = trace_reduce.reduce(trace, t0, t1, HOST_SPANS)
        shutil.rmtree(trace_dir, ignore_errors=True)
        info["busy_s"] = red["busy_s"]
        info["window_s"] = red["window_s"]
        run = {"window": win, "trace": red, "config": cell.c, "traffic": cell.traffic,
               "peak": peak_for(info["kind"]), "costs": costs}
        metrics = {}
        for m in metric_names(spec, args.workload, "per_layer"):
            value = importlib.import_module(f"metrics.{m['name']}").read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        out["breakdown"] = {"device_ops": red["device_ops"], "idle_gaps": red["idle_gaps"]}
    else:
        e2e = {"samples_per_s": win.samples / win.seconds, "setup_s": setup_s}
        if win.latencies_s:
            e2e["infer_p95_ms"] = harness.percentile(win.latencies_s, 95) * 1e3
        metrics = {}
        for m in metric_names(spec, args.workload, "end_to_end"):
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}

    nums, notes = cell.check()
    ok, checks = verdict(nums, cell.limits)
    host = [f"window: {win.rasters} rasters, {win.samples} samples in {win.seconds!r} s; "
            f"set-up {setup_s!r} s",
            f"host per raster: data_wait {win.data_wait_s / max(win.rasters, 1) * 1e3!r} ms, "
            f"host_loop {win.host_loop_s / max(win.rasters, 1) * 1e3!r} ms; "
            f"garbage collection {win.gc_s!r} s in the window",
            f"rasters per second of the window: {harness.per_second(win.ends_s)}"]
    for line in notes + host:
        print(line, flush=True)
    result = {"correct": ok, "attempted": win.rasters, "failed": 0, "metrics": metrics,
              "device": info, **out, "checks": checks}
    print(json.dumps(result), flush=True)
    for name, v in checks.items():
        print(f"check {name} {v['value']!r} limit {v['limit']!r}", file=sys.stderr, flush=True)
    return 0


def peak_for(kind: str) -> dict:
    peaks = harness.load_json(harness.BENCH / "peaks.json")
    if kind not in peaks:
        raise SystemExit(f"bench: no peak for device kind {kind!r} in peaks.json")
    return peaks[kind]


if __name__ == "__main__":
    sys.exit(main())
