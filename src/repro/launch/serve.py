"""Serving launcher: ``python -m repro.launch.serve [...]``.

Brings up the online-plasticity :class:`repro.serve.Server`, submits a
synthetic per-session spike-raster load (each session is one user's
private network, learning continually via the selected rule × backend),
and reports step latency, throughput, and the session-memory numbers
that make the packed-word "plasticity cache" the headline: bytes per
session and sessions per GiB.

``--ckpt-dir`` saves the full session store on exit and restores from
the latest checkpoint on startup, so a long-running deployment's learned
per-user state survives restarts.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.launch.cli import (add_serve_flags, add_update_flags,
                              engine_config_from_args, serve_config_from_args)
from repro.launch.compile_cache import enable_compile_cache
from repro.serve import Request, Server


def synthetic_load(key, *, sessions: int, requests: int, t_steps: int,
                   n_pre: int, rate: float = 0.3) -> list[Request]:
    """A deterministic request stream over ``sessions`` round-robin users."""
    reqs = []
    for i in range(requests):
        sub = jax.random.fold_in(key, i)
        raster = (jax.random.uniform(sub, (t_steps, n_pre)) < rate)
        reqs.append(Request(sid=f"user{i % sessions}",
                            raster=raster.astype(np.float32)))
    return reqs


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    add_serve_flags(ap)
    add_update_flags(ap)
    ap.add_argument("--sessions", type=int, default=8,
                    help="distinct synthetic users in the load")
    ap.add_argument("--requests", type=int, default=32,
                    help="total requests submitted")
    ap.add_argument("--rate", type=float, default=0.3,
                    help="per-step input spike probability of the load")
    ap.add_argument("--ckpt-dir", default=None,
                    help="restore latest checkpoint on start, save on exit")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = engine_config_from_args(args)
    scfg = serve_config_from_args(args)
    server = Server(cfg, scfg, seed=args.seed)
    if args.ckpt_dir:
        try:
            server.restore(args.ckpt_dir)
            print(f"restored {len(server.store)} sessions "
                  f"from {args.ckpt_dir}")
        except FileNotFoundError:
            print(f"no checkpoint under {args.ckpt_dir}; starting fresh")

    reqs = synthetic_load(jax.random.PRNGKey(args.seed + 1),
                          sessions=args.sessions, requests=args.requests,
                          t_steps=scfg.t_steps, n_pre=cfg.n_pre,
                          rate=args.rate)
    tickets = [server.submit(r) for r in reqs]

    # first step compiles; time the steady state separately
    t0 = time.perf_counter()
    server.step()
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    served = server.shutdown(drain=True)
    dt = time.perf_counter() - t0

    done = sum(server.poll(t) is not None for t in tickets)
    store = server.store
    steps = served * scfg.t_steps
    print(f"served {done}/{args.requests} requests "
          f"({args.sessions} sessions, rule={cfg.rule}, "
          f"backend={cfg.backend})")
    print(f"  first step (compile): {compile_s * 1e3:.1f} ms; drain: "
          f"{served} lanes / {steps} sim-steps in {dt:.3f}s "
          f"({steps / max(dt, 1e-9):.0f} steps/s)")
    print(f"  plasticity cache: {store.state_bytes_per_session()} B/session "
          f"({store.sessions_per_gb():.0f} sessions/GiB); resident "
          f"{store.resident_bytes_per_session()} B/session "
          f"({store.sessions_per_gb(resident=True):.0f} sessions/GiB)")

    if args.ckpt_dir:
        path = server.checkpoint(args.ckpt_dir)
        print(f"  checkpointed {len(store)} sessions -> {path}")


if __name__ == "__main__":
    main()
