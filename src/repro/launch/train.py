"""Training launcher for the paper's learning engine and networks.

Two modes; one of them must be chosen:

``python -m repro.launch.train --snn <net>`` trains one of the paper's
networks (2-layer SNN, 6-layer DCSNN, 5-layer CSNN) with the shared
train-to-accuracy loop of ``repro.train.stdp_trainer``: unsupervised STDP
epochs with homeostasis/WTA competition and the label-assignment
evaluation, built from the same CLI flags (``repro.launch.cli``) as
``examples/train_snn.py``.  The conv nets drive the rule's im2col-fused
conv kernel, the fc layers its dense kernel.

``python -m repro.launch.train --engine`` trains a population of engine
replicas on random rasters and reports synaptic-op throughput.

Both modes take the learning rule (``--rule itp|itp_nocomp|exact|linear|
imstdp|mstdp``) and the weight-update backend (``--backend reference|
fused|fused_interpret|sparse``; ``--max-events`` caps the sparse event
lists), so the full rule × backend matrix in ROADMAP.md runs from here.
A rule without a kernel for the chosen backend is rejected up front with
the valid combinations.
"""
from __future__ import annotations

import argparse
import time

import jax

from repro.launch import cli
from repro.launch.compile_cache import enable_compile_cache


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
    ap.add_argument("--engine", action="store_true",
                    help="train a population of learning-engine replicas")
    # SNN-mode flags come from the shared builder (repro.launch.cli) so
    # this entry point and examples/train_snn.py declare them exactly once;
    # --snn doubles as the mode switch (default None = engine mode or a
    # usage error); --batch keeps this launcher's default of 8
    cli.add_net_flag(ap, "--snn", default=None)
    cli.add_update_flags(ap)
    cli.add_train_flags(ap, batch_default=8)
    ap.add_argument("--engine-pre", type=int, default=256)
    ap.add_argument("--engine-post", type=int, default=256)
    ap.add_argument("--replicas", type=int, default=8)
    ap.add_argument("--engine-rate", type=float, default=0.3,
                    help="Bernoulli input spike rate (--engine mode)")
    ap.add_argument("--steps", type=int, default=100)
    args = ap.parse_args()
    if not args.net and not args.engine:
        ap.error("choose a mode: --snn <net> or --engine")
    enable_compile_cache()

    if args.net:
        run_snn_training(args)
    else:
        run_engine_training(args)


if __name__ == "__main__":
    main()
