"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

Runs end-to-end on whatever devices exist (CPU smoke scale → TPU pods): a
synthetic-token LM run with the full production control loop — sharded
init, jitted train step, async checkpointing, restart-on-failure,
straggler watchdog.  For the paper's own SNN training path use
``examples/train_snn.py`` (the learning-engine loop has no gradients).

``--engine`` switches to the learning-engine workload: a population of
engine replicas trained on random rasters with the selectable learning
rule (``--rule itp|itp_nocomp|exact|linear|imstdp``) and weight-update
backend (``--backend reference|fused|fused_interpret|sparse``),
reporting synaptic-op throughput — the launcher path for exercising the
fused Pallas datapath (and the counter-rule baselines) end-to-end.  The
``sparse`` backend is the event-driven datapath (``--max-events`` caps
the static event-list length per side).

``--snn <net>`` switches to the paper's network workloads (2-layer SNN,
6-layer DCSNN, 5-layer CSNN) on the same selectable rule and backend,
driving the shared train-to-accuracy loop of
``repro.train.stdp_trainer`` — unsupervised STDP epochs with
homeostasis/WTA competition and the label-assignment evaluation — through
the same CLI builder (``repro.launch.cli``) as ``examples/train_snn.py``:
the conv nets drive the rule's im2col-fused conv kernel, the fc layers
its dense engine kernel — the launcher path for the whole-network fused
datapath.  Every registered rule is kernel-backed (history rules →
``itp_stdp``/``itp_stdp_conv``, counter rules → ``itp_counter``), so the
full rule × backend matrix in ROADMAP.md runs from here; a rule without
a kernel would still be rejected up front with the valid combinations.
"""
from __future__ import annotations

import argparse
import time

import jax

from repro.configs import ARCH_NAMES, get_config, get_smoke_config
from repro.data import LMBatchSpec, lm_batches
from repro.distributed.fault_tolerance import (FailureInjector, RunnerConfig,
                                               TrainingRunner)
from repro.distributed.sharding import use_mesh
from repro.launch import cli
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import describe, make_debug_mesh
from repro.train import (OptimizerConfig, TrainConfig, init_training,
                         make_train_step)


def run_engine_training(args) -> dict:
    """Population engine training on the selected rule + backend.

    Trains ``--replicas`` independent engine replicas for ``--steps`` steps
    on Bernoulli rasters and reports wall-clock + synaptic-op throughput.
    Returns the summary dict (also printed) so tests can call this directly.
    """
    from repro.core.engine import (EngineConfig, init_engine_population,
                                   run_engine_population)

    rule = getattr(args, "rule", "itp")
    cfg = EngineConfig(n_pre=args.engine_pre, n_post=args.engine_post,
                       rule=rule, backend=args.backend,
                       max_events=getattr(args, "max_events", None))
    key = jax.random.PRNGKey(0)
    states = init_engine_population(key, cfg, args.replicas)
    trains = jax.random.bernoulli(
        jax.random.fold_in(key, 1), args.engine_rate,
        (args.replicas, args.steps, cfg.n_pre))

    run = jax.jit(lambda s, x: run_engine_population(s, x, cfg))
    t0 = time.time()
    states, post = jax.block_until_ready(run(states, trains))
    compile_s = time.time() - t0
    t0 = time.time()
    states, post = jax.block_until_ready(run(states, trains))
    run_s = time.time() - t0

    sops = args.replicas * args.steps * cfg.n_pre * cfg.n_post
    summary = {
        "rule": rule,
        "backend": args.backend,
        "replicas": args.replicas,
        "n_pre": cfg.n_pre, "n_post": cfg.n_post, "steps": args.steps,
        "compile_seconds": round(compile_s, 3),
        "run_seconds": round(run_s, 4),
        "sops_per_s": sops / max(run_s, 1e-9),
        "mean_post_rate": float(post.mean()),
    }
    print(f"engine training [{rule} / {args.backend}]: "
          f"{args.replicas} replicas × "
          f"{cfg.n_pre}×{cfg.n_post} × {args.steps} steps — "
          f"{summary['sops_per_s']:.3e} SOP/s "
          f"(compile {compile_s:.2f}s, run {run_s:.3f}s, "
          f"mean post rate {summary['mean_post_rate']:.3f})", flush=True)
    return summary


def run_snn_training(args) -> dict:
    """One of the paper's SNNs, trained to accuracy on rule + backend.

    Drives the shared train-to-accuracy loop
    (``repro.train.stdp_trainer``) — epochs of unsupervised STDP over
    rate-coded stand-in data with the label-assignment evaluation after
    each — through the same ``SNNConfig`` / ``TrainerConfig`` builders as
    ``examples/train_snn.py`` (``repro.launch.cli``).  The conv nets
    (6layer-dcsnn, 5layer-csnn) exercise the im2col-fused conv kernel
    end-to-end.  Reports accuracy plus wall-clock + synaptic-update
    throughput; returns the summary dict (also printed) so tests can call
    this directly, including with legacy ``--steps``-style namespaces.
    """
    from repro.launch import cli
    from repro.models import snn
    from repro.train.stdp_trainer import train_to_accuracy

    net = cli.net_from_args(args)
    cfg = cli.snn_config_from_args(args, net=net)
    tcfg = cli.trainer_config_from_args(args)
    sampler, n_classes = cli.sampler_for(net)
    result = train_to_accuracy(cfg, sampler, n_classes, tcfg, verbose=True)

    # synaptic updates per step: every learnable layer touches its full
    # (fan_in × out) matrix per patch row
    updates = 0
    shapes = [tuple(cfg.input_shape)] + snn._layer_shapes(cfg)
    for spec, in_shape, out_shape in zip(cfg.layers, shapes[:-1], shapes[1:]):
        if spec.kind.startswith("pool"):
            continue
        rows = 1
        for d in out_shape[:-1] or (1,):
            rows *= d
        updates += tcfg.batch * rows * snn._fan_in(spec, in_shape) \
            * spec.out_features
    run_s = result["train_seconds"]
    summary = {
        "net": cfg.name, "rule": cfg.rule, "backend": cfg.backend,
        "batch": tcfg.batch,
        "steps": result["sim_steps"],
        "epochs": tcfg.epochs,
        "run_seconds": round(run_s, 4),
        "sops_per_s": result["sim_steps"] * updates / max(run_s, 1e-9),
        "mean_rate": result["mean_eval_rates"][-1],
        "accuracy_curve": result["accuracy_curve"],
        "final_accuracy": result["final_accuracy"],
        "chance": result["chance"],
    }
    print(f"snn training [{cfg.name} / {cfg.rule} / {cfg.backend}]: "
          f"batch {tcfg.batch} × {result['sim_steps']} steps — "
          f"{summary['sops_per_s']:.3e} SOP/s (train {run_s:.2f}s incl. "
          f"compile), accuracy {summary['final_accuracy']:.3f} "
          f"(chance {summary['chance']:.3f})", flush=True)
    return summary


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES, default="qwen3-0.6b")
    ap.add_argument("--engine", action="store_true",
                    help="train the ITP-STDP learning engine instead of the "
                         "LM stack")
    # SNN-mode flags come from the shared builder (repro.launch.cli) so
    # this entry point and examples/train_snn.py declare them exactly once;
    # --snn doubles as the mode switch (default None = LM/engine mode) and
    # --batch is shared with the LM path (hence the LM default of 8)
    cli.add_net_flag(ap, "--snn", default=None)
    cli.add_update_flags(ap)
    cli.add_train_flags(ap, batch_default=8)
    ap.add_argument("--engine-pre", type=int, default=256)
    ap.add_argument("--engine-post", type=int, default=256)
    ap.add_argument("--replicas", type=int, default=8)
    ap.add_argument("--engine-rate", type=float, default=0.3,
                    help="Bernoulli input spike rate (--engine and --snn "
                         "modes)")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--remat", choices=("none", "full", "dots"),
                    default="none")
    ap.add_argument("--po2-update", action="store_true",
                    help="ITP-AdamW: po2-quantised optimizer updates")
    ap.add_argument("--data", type=int, default=0,
                    help="data-parallel mesh axis (0 = no mesh)")
    ap.add_argument("--model", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--inject-failure-at", type=int, default=-1)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()
    enable_compile_cache()

    if args.net:
        run_snn_training(args)
        return
    if args.engine:
        run_engine_training(args)
        return

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    opt_cfg = OptimizerConfig(lr=args.lr, total_steps=args.steps,
                              warmup_steps=max(args.steps // 20, 5),
                              po2_update=args.po2_update)
    train_cfg = TrainConfig(remat=args.remat)

    mesh = None
    if args.data > 0:
        mesh = make_debug_mesh(data=args.data, model=args.model)
        print(f"mesh: {describe(mesh)}")

    ctx = use_mesh(mesh) if mesh is not None else use_mesh(None)
    with ctx:
        params, opt_state = init_training(jax.random.PRNGKey(0), cfg, opt_cfg,
                                          mesh)
        step_fn = jax.jit(make_train_step(cfg, opt_cfg, train_cfg, mesh))

        spec = LMBatchSpec(batch=args.batch, seq=args.seq,
                           vocab=cfg.vocab_size)

        def batch_for(step: int):
            return next(lm_batches(jax.random.PRNGKey(1000 + step), spec,
                                   n_steps=1))

        state = {"params": params, "opt": opt_state}

        def wrapped(state, batch):
            p, o, metrics = step_fn(state["params"], state["opt"], batch)
            return {"params": p, "opt": o}, metrics

        runner = TrainingRunner(
            RunnerConfig(ckpt_dir=args.ckpt_dir,
                         ckpt_every=args.ckpt_every),
            wrapped, batch_for)
        injector = None
        if args.inject_failure_at >= 0:
            injector = FailureInjector({args.inject_failure_at})

        t0 = time.time()
        n_logged = [0]

        orig_step = runner.step_fn

        def logging_step(state, batch):
            out, metrics = orig_step(state, batch)
            n = n_logged[0]
            if n % args.log_every == 0:
                loss = float(metrics["loss"])
                toks = float(metrics["tokens"]) * args.log_every
                dt = time.time() - t0
                print(f"step {n:5d}  loss {loss:.4f}  "
                      f"lr {float(metrics['lr']):.2e}  "
                      f"gnorm {float(metrics['grad_norm']):.3f}  "
                      f"({n / max(dt, 1e-9):.2f} it/s)", flush=True)
            n_logged[0] += 1
            return out, metrics

        runner.step_fn = logging_step
        state = runner.run(state, args.steps, injector)
        print(f"done: {args.steps} steps in {time.time() - t0:.1f}s; "
              f"restarts={runner.restarts}; "
              f"stragglers={len(runner.watchdog.stragglers)}")


if __name__ == "__main__":
    main()
