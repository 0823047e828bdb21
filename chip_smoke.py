"""Smoke run of the fused ITP-STDP datapath on a TPU.

    python chip_smoke.py               # one chip: phases (a)-(e)
    python chip_smoke.py --four-chips  # four chips: the sharded engine only

Everything runs in this one process, in order, and each phase prints one
line.  With no option:

  (a) the first JAX device must be a TPU;
  (b) 2layer-snn at the paper's width (784 -> 100, rule ``itp``, backend
      ``fused``) trains one short epoch through the shared CLI builders
      and the train-to-accuracy loop; its compiled ``run_snn`` must hold a
      Pallas kernel (``tpu_custom_call``), and one training raster from
      the same initial state is compared with backend ``reference``;
  (c) 6layer-dcsnn does the same on the conv kernel;
  (d) engine training with rules ``exact`` and ``imstdp`` on ``fused``
      (the counter kernel and its SMEM window table), compared with
      ``reference``;
  (e) the online-plasticity ``Server`` on ``fused`` answers requests for
      a few sessions and drains, compared with a ``reference`` server.

With ``--four-chips``: (a), then the 2-D sharded engine on a 2x2 mesh at
4096 x 4096 against single-device ``engine_step`` on the same spikes.

Every phase passes ``backend="fused"`` itself, so nothing on this path
can fall back to the Pallas interpreter.  A failed check raises: the exit
code is then non-zero and no result line is printed.  The last line of
stdout is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.engine import (EngineConfig, engine_step,  # noqa: E402
                               init_engine, init_engine_population, run_engine,
                               run_engine_population)
from repro.core.engine_sharded import (make_sharded_engine_step,  # noqa: E402
                                       shard_engine_state)
from repro.data.pipeline import encode_batch  # noqa: E402
from repro.launch import cli  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_debug_mesh  # noqa: E402
from repro.launch.train import run_engine_training  # noqa: E402
from repro.models import snn  # noqa: E402
from repro.serve import Request, ServeConfig, Server  # noqa: E402
from repro.serve.serving import _batched_rollout  # noqa: E402
from repro.train.stdp_trainer import train_to_accuracy  # noqa: E402

BACKEND = "fused"
# fused-vs-reference bound on max |Δw| after one training raster: the
# tolerance the CPU parity tests hold the interpreted kernels to.  The
# comparison runs with weight quantisation off, so it reads the raw f32
# update rather than the 1/127 grid, where one rounding tie would show as
# a whole level.  Both sides run at f32 matmul precision: at the TPU's
# default precision the reference einsum rounds its f32 magnitudes to
# bf16, while the kernels contract at full f32.
MAX_ABS_DW = 1e-5
F32 = "highest"
# Input spike probability per neuron and step for the engine and serving
# phases.  At the default weights (U(0.2, 0.8)) and LIF threshold these
# keep the mean post rate well inside (0, 1), so the current sum decides
# every spike and both the LTP and the LTD branch of the update run; at a
# saturating input every post neuron fires every step and LTD never does.
ENGINE_RATE = 2e-3  # 784 inputs: ~1.6 input spikes per step
POST_RATE = (0.05, 0.6)
# --four-chips: a lane-aligned square engine, weights split over 2x2 chips
SHARDED_N, SHARDED_STEPS, SHARDED_RATE = 4096, 16, 3e-4  # ~1.2 spikes/step


def _line(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def _require_kernel(compiled, what: str) -> None:
    """The compiled program must call a Mosaic kernel, not interpret it."""
    if "tpu_custom_call" not in compiled.as_text():
        raise AssertionError(f"{what}: no tpu_custom_call in the compiled program")


def _max_abs(a, b) -> float:
    leaves = zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b))
    return max(float(jnp.max(jnp.abs(x - y))) for x, y in leaves)


def _require_close(what: str, diff: float) -> None:
    if not diff <= MAX_ABS_DW:
        raise AssertionError(f"{what}: max|dw| {diff!r} exceeds {MAX_ABS_DW}")


def _require_plasticity(what: str, post_rate: float, w, w0) -> tuple[int, int]:
    """Post rate inside POST_RATE, and weights both rose (LTP) and fell (LTD)."""
    lo, hi = POST_RATE
    if not lo <= post_rate <= hi:
        raise AssertionError(f"{what}: mean post rate {post_rate!r} outside [{lo}, {hi}]")
    n_ltp = int(jnp.sum(w > w0))
    n_ltd = int(jnp.sum(w < w0))
    if n_ltp == 0 or n_ltd == 0:
        raise AssertionError(f"{what}: {n_ltp} weights rose and {n_ltd} fell; both must move")
    return n_ltp, n_ltd


def phase_device(want_count: int) -> dict:
    dev = jax.devices()[0]
    info = {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())}
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found (first device: {dev.platform})")
    if info["count"] < want_count:
        raise SystemExit(f"chip_smoke: needs {want_count} TPU devices, found {info['count']}")
    _line("a", f"device_kind={info['kind']} count={info['count']}")
    return info


def phase_network(tag: str, argv: list[str]) -> None:
    """Train a paper network through the CLI builders, then check it."""
    ap = argparse.ArgumentParser()
    cli.add_net_flag(ap, "--net")
    cli.add_update_flags(ap)
    cli.add_train_flags(ap)
    args = ap.parse_args(argv + ["--backend", BACKEND])
    cfg = cli.snn_config_from_args(args)
    tcfg = cli.trainer_config_from_args(args)
    sampler, n_classes = cli.sampler_for(args.net)

    key = jax.random.PRNGKey(tcfg.seed)
    state = snn.init_snn(key, cfg, tcfg.batch)
    x, _ = sampler(jax.random.fold_in(key, 1), tcfg.batch)
    raster = encode_batch(jax.random.fold_in(key, 2), x, tcfg.t_steps)
    t0 = time.perf_counter()
    _require_kernel(snn.run_snn.lower(state, raster, cfg, train=True).compile(), cfg.name)
    compile_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    result = train_to_accuracy(cfg, sampler, n_classes, tcfg)
    train_s = time.perf_counter() - t0
    weights = result["state"].weights
    if not all(bool(jnp.all((w >= 0.0) & (w <= 1.0))) for w in weights):
        raise AssertionError(f"{cfg.name}: trained weights left [0, 1]")
    if not 0.0 <= result["final_accuracy"] <= 1.0:
        raise AssertionError(f"{cfg.name}: accuracy {result['final_accuracy']!r}")

    # one training raster from the same initial state, fused vs reference
    raw = dataclasses.replace(cfg, quantise=False)
    ref = dataclasses.replace(raw, backend="reference")
    with jax.default_matmul_precision(F32):
        s_f, c_f = snn.run_snn(state, raster, raw, train=True)
        s_r, c_r = snn.run_snn(state, raster, ref, train=True)
    diff = _max_abs(s_f.weights, s_r.weights)
    # The reason for comparing at f32: with both sides at the TPU's default
    # precision, the reference rounds its magnitudes to bf16 and drifts
    # from the kernels, which contract at HIGHEST.  A reading, not a check.
    s_fd, _ = snn.run_snn(state, raster, raw, train=True)
    s_rd, _ = snn.run_snn(state, raster, ref, train=True)
    diff_default = _max_abs(s_fd.weights, s_rd.weights)
    moved = _max_abs(s_f.weights, state.weights)
    _require_close(cfg.name, diff)
    if moved == 0.0:
        raise AssertionError(f"{cfg.name}: one raster changed no weight")
    counts_diff = int(jnp.sum(c_f != c_r))
    _line(
        tag,
        f"{cfg.name} rule={cfg.rule} backend={cfg.backend} batch={tcfg.batch} "
        f"t_raster={tcfg.t_steps} train_batches={tcfg.batches_per_epoch}: "
        f"accuracy={result['final_accuracy']!r} train_s={train_s!r} "
        f"tpu_custom_call=yes compile_s={compile_s!r} "
        f"fused_vs_reference max|dw|={diff!r} (bound {MAX_ABS_DW}) "
        f"at default matmul precision max|dw|={diff_default!r} "
        f"max|w-w0|={moved!r} spike_count_mismatches={counts_diff}",
    )


def phase_engine(rule: str) -> None:
    """Engine training on a counter rule via the launcher's entry point."""
    args = argparse.Namespace(
        rule=rule,
        backend=BACKEND,
        engine_pre=784,
        engine_post=128,
        replicas=4,
        steps=30,
        engine_rate=ENGINE_RATE,
    )
    summary = run_engine_training(args)

    cfg = EngineConfig(n_pre=args.engine_pre, n_post=args.engine_post, rule=rule, backend=BACKEND)
    ref = dataclasses.replace(cfg, backend="reference")
    key = jax.random.PRNGKey(0)
    states = init_engine_population(key, cfg, args.replicas)
    trains = jax.random.bernoulli(
        jax.random.fold_in(key, 1), args.engine_rate, (args.replicas, args.steps, cfg.n_pre)
    )
    with jax.default_matmul_precision(F32):
        compiled = (
            jax.jit(lambda s, x: run_engine_population(s, x, cfg)).lower(states, trains).compile()
        )
        _require_kernel(compiled, f"engine {rule}")
        s_f, post_f = compiled(states, trains)
        s_r, post_r = jax.jit(lambda s, x: run_engine_population(s, x, ref))(states, trains)
    diff = _max_abs(s_f.w, s_r.w)
    _require_close(f"engine {rule}", diff)
    post_rate = float(jnp.mean(post_f))
    n_ltp, n_ltd = _require_plasticity(f"engine {rule}", post_rate, s_f.w, states.w)
    _line(
        "d",
        f"engine rule={rule} backend={BACKEND} {args.replicas}x{cfg.n_pre}x{cfg.n_post} "
        f"x {args.steps} steps, input rate {args.engine_rate}: "
        f"compile_s={summary['compile_seconds']!r} tpu_custom_call=yes "
        f"fused_vs_reference max|dw|={diff!r} (bound {MAX_ABS_DW}) "
        f"post_rate={post_rate!r} weights_up={n_ltp} weights_down={n_ltd} "
        f"max|w-w0|={_max_abs(s_f.w, states.w)!r} "
        f"post_spike_mismatches={int(jnp.sum(post_f != post_r))}",
    )


def _serve(cfg: EngineConfig, scfg: ServeConfig, reqs: list[Request], *, threaded: bool):
    server = Server(cfg, scfg, seed=0)
    tickets = [server.submit(r) for r in reqs]
    if threaded:
        server.start()
    server.shutdown(drain=True)
    results = [server.poll(t) for t in tickets]
    if any(r is None for r in results):
        raise AssertionError(f"serve {cfg.backend}: {results.count(None)} requests unanswered")
    return server, results


def phase_serve() -> None:
    """The online-plasticity server on the fused datapath, then drained."""
    cfg = EngineConfig(n_pre=784, n_post=128, rule="itp", backend=BACKEND)
    scfg = ServeConfig(max_batch=4, t_steps=16)
    sessions, n_req = 4, 12
    key = jax.random.PRNGKey(1)
    reqs = [
        Request(
            sid=f"user{i % sessions}",
            raster=np.asarray(
                jax.random.bernoulli(
                    jax.random.fold_in(key, i), ENGINE_RATE, (scfg.t_steps, cfg.n_pre)
                ),
                np.float32,
            ),
        )
        for i in range(n_req)
    ]
    t0 = time.perf_counter()
    with jax.default_matmul_precision(F32):
        server, res_f = _serve(cfg, scfg, reqs, threaded=True)
        serve_s = time.perf_counter() - t0
        ref_cfg = dataclasses.replace(cfg, backend="reference")
        ref_server, res_r = _serve(ref_cfg, scfg, reqs, threaded=False)
        template = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *[server.store.fresh_state("x")] * scfg.max_batch
        )
        rasters = jnp.zeros((scfg.max_batch, scfg.t_steps, cfg.n_pre), jnp.float32)
        compiled = _batched_rollout.lower(
            cfg, scfg, True, template.w, template.pre_words, template.post_words,
            template.v, template.theta, rasters,
        ).compile()
    _require_kernel(compiled, "serve step")
    for r in res_f:
        if r.post.shape != (scfg.t_steps, cfg.n_post) or r.post.max() > 1:
            raise AssertionError(f"serve: bad post raster for {r.sid}: {r.post.shape}")
    sids = [f"user{i}" for i in range(sessions)]
    diff = max(_max_abs(server.store.peek(s).w, ref_server.store.peek(s).w) for s in sids)
    _require_close("serve", diff)
    mismatches = sum(int(np.sum(a.post != b.post)) for a, b in zip(res_f, res_r))
    post_rate = float(np.mean([r.post for r in res_f]))
    w = jnp.stack([server.store.peek(s).w for s in sids])
    w0 = jnp.stack([server.store.fresh_state(s).w for s in sids])
    n_ltp, n_ltd = _require_plasticity("serve", post_rate, w, w0)
    _line(
        "e",
        f"serve rule={cfg.rule} backend={BACKEND} {cfg.n_pre}x{cfg.n_post}: "
        f"{len(res_f)}/{n_req} requests answered for {sessions} sessions in "
        f"{serve_s!r}s incl. compile, drained; tpu_custom_call=yes "
        f"fused_vs_reference max|dw|={diff!r} (bound {MAX_ABS_DW}) "
        f"post_rate={post_rate!r} weights_up={n_ltp} weights_down={n_ltd} "
        f"post_spike_mismatches={mismatches}",
    )


def phase_sharded() -> None:
    """2-D weight-sharded engine on a 2x2 mesh vs single-device engine_step."""
    n, steps = SHARDED_N, SHARDED_STEPS
    cfg = EngineConfig(n_pre=n, n_post=n, rule="itp", backend=BACKEND)
    key = jax.random.PRNGKey(0)
    state0 = init_engine(key, cfg)
    train = jax.random.bernoulli(jax.random.fold_in(key, 1), SHARDED_RATE, (steps, n))
    mesh = make_debug_mesh(data=2, model=2)
    with jax.default_matmul_precision(F32):
        ref_state, ref_post = jax.jit(lambda s, x: run_engine(s, x, cfg))(state0, train)
        one = jax.jit(lambda s, x: engine_step(s, x, cfg))
        _require_kernel(one.lower(state0, train[0]).compile(), "engine_step")
        with mesh:
            st = shard_engine_state(state0, mesh)
            step = make_sharded_engine_step(cfg, mesh)
            _require_kernel(step.lower(st, train[0]).compile(), "sharded engine step")
            t0 = time.perf_counter()
            posts = []
            for t in range(steps):
                st, post = step(st, train[t])
                posts.append(post)
            jax.block_until_ready(st.w)
            run_s = time.perf_counter() - t0
    if len(st.w.sharding.device_set) != 4:
        raise AssertionError(f"sharded weights live on {len(st.w.sharding.device_set)} devices")
    diff = _max_abs(st.w, ref_state.w)
    moved = _max_abs(st.w, state0.w)
    _require_close("sharded engine", diff)
    post = jnp.stack(posts)
    mismatches = int(jnp.sum(post != ref_post))
    post_rate = float(jnp.mean(post))
    n_ltp, n_ltd = _require_plasticity("sharded engine", post_rate, st.w, state0.w)
    _line(
        "4",
        f"sharded engine rule={cfg.rule} backend={BACKEND} {n}x{n} on mesh "
        f"data=2 x model=2, {steps} steps, input rate {SHARDED_RATE}: vs single-device "
        f"engine_step max|dw|={diff!r} (bound {MAX_ABS_DW}) max|w-w0|={moved!r} "
        f"post_rate={post_rate!r} weights_up={n_ltp} weights_down={n_ltd} "
        f"post_spike_mismatches={mismatches} run_s={run_s!r}",
    )


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--four-chips",
        action="store_true",
        help="run only the sharded engine on a 2x2 mesh of four chips",
    )
    args = ap.parse_args()
    enable_compile_cache()
    device = phase_device(4 if args.four_chips else 1)
    if args.four_chips:
        phase_sharded()
    else:
        train = ["--epochs", "1", "--batch", "16", "--t-raster", "30"]
        phase_network(
            "b",
            ["--net", "2layer-snn", "--rule", "itp", "--batches-per-epoch", "4"]
            + train
            + ["--assign-batches", "2", "--eval-batches", "2"],
        )
        phase_network(
            "c",
            ["--net", "6layer-dcsnn", "--rule", "itp", "--batches-per-epoch", "2"]
            + train
            + ["--assign-batches", "1", "--eval-batches", "1"],
        )
        phase_engine("exact")
        phase_engine("imstdp")
        phase_serve()
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
