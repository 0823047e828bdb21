"""Benchmark: Table II — accuracy parity of STDP variants across the
paper's three networks.

Protocol (identical across rules, so differences isolate the rule):
unsupervised STDP feature learning → frozen features → ridge readout.
Datasets are the synthetic stand-ins (MNIST & co. are not available
offline — docs/architecture.md §7); the claim under test is *parity* between
original STDP, ITP-STDP (comp.) and ITP-STDP (w/o comp.), which the paper
reports as ≤ ~0.4 pp spread on MNIST and no systematic degradation."""
from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp

from repro.data import (encode_batch, synthetic_digits, synthetic_fashion,
                        synthetic_fault)
from repro.models import snn

PAPER_TABLE_II = {
    "2layer-snn": {"exact": 94.28, "itp": 94.26, "itp_nocomp": 94.13},
    "6layer-dcsnn": {"exact": 86.85, "itp": 91.25, "itp_nocomp": 91.10},
    "5layer-csnn": {"exact": 88.10, "itp": 98.15, "itp_nocomp": 97.76},
}

NETWORKS = {
    "2layer-snn": (snn.mnist_2layer,
                   lambda k, n: synthetic_digits(k, n), 10),
    "6layer-dcsnn": (snn.fmnist_dcsnn,
                     lambda k, n: synthetic_fashion(k, n), 10),
    "5layer-csnn": (snn.fault_csnn,
                    lambda k, n: synthetic_fault(k, n, length=512), 4),
}

RULES = ("exact", "itp", "itp_nocomp")


def eval_network(cfg, sampler, n_classes, *, n_train=96, n_test=64,
                 T=30, B=16, seed=0) -> float:
    key = jax.random.PRNGKey(seed)
    st = snn.init_snn(key, cfg, B)
    k = key
    for _ in range(n_train // B):
        k, kd, ke = jax.random.split(k, 3)
        x, _ = sampler(kd, B)
        st, _ = snn.run_snn(st, encode_batch(ke, x, T), cfg, train=True)
        st = snn.reset_dynamics(st, cfg, B)

    def feats(n, seed2):
        fs, ls = [], []
        kk = jax.random.PRNGKey(seed2)
        s = st
        for _ in range(n // B):
            kk, kd, ke = jax.random.split(kk, 3)
            x, y = sampler(kd, B)
            s = snn.reset_dynamics(s, cfg, B)
            s, c = snn.run_snn(s, encode_batch(ke, x, T), cfg, train=False)
            fs.append(c)
            ls.append(y)
        return jnp.concatenate(fs), jnp.concatenate(ls)

    Xtr, ytr = feats(n_train, 1000 + seed)
    Xte, yte = feats(n_test, 2000 + seed)
    W = snn.fit_readout(Xtr, ytr, n_classes)
    return snn.readout_accuracy(W, Xte, yte)


def run(out_dir: str = "experiments/bench", verbose: bool = True,
        n_train: int = 96, n_test: int = 64, seeds=(0, 1)) -> dict:
    results: dict = {}
    for net, (maker, sampler, n_classes) in NETWORKS.items():
        results[net] = {}
        for rule in RULES:
            accs = []
            for seed in seeds:
                cfg = maker(rule)
                t0 = time.time()
                acc = eval_network(cfg, sampler, n_classes,
                                   n_train=n_train, n_test=n_test,
                                   seed=seed)
                accs.append(acc)
            results[net][rule] = {
                "mean": float(sum(accs) / len(accs)),
                "accs": [float(a) for a in accs],
            }
        vals = [results[net][r]["mean"] for r in RULES]
        results[net]["parity_spread"] = float(max(vals) - min(vals))
        results[net]["chance"] = 1.0 / n_classes

    out = {"results": results, "paper_table_ii": PAPER_TABLE_II,
           "protocol": {"n_train": n_train, "n_test": n_test,
                        "t_steps": 30, "seeds": list(seeds)}}
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "network_accuracy.json"), "w") as f:
        json.dump(out, f)
    if verbose:
        print("— network accuracy parity (paper Table II) —")
        print(f"  {'network':14s} {'exact':>8s} {'itp':>8s} "
              f"{'nocomp':>8s} {'spread':>8s} {'chance':>7s}")
        for net in NETWORKS:
            r = results[net]
            print(f"  {net:14s} "
                  f"{r['exact']['mean']:8.3f} {r['itp']['mean']:8.3f} "
                  f"{r['itp_nocomp']['mean']:8.3f} "
                  f"{r['parity_spread']:8.3f} {r['chance']:7.2f}")
        print("  (synthetic stand-in data: the tested claim is parity "
              "between rules, not absolute accuracy)")
    return out


if __name__ == "__main__":
    run()
