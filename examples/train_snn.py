"""End-to-end driver: train one of the paper's SNNs with ITP-STDP to
classification accuracy.

Epochs of unsupervised STDP over rate-coded synthetic stand-in data with
intra-layer competition (soft lateral inhibition / ``--hard-wta``) and
adaptive-threshold homeostasis (``--theta-plus`` / ``--theta-tau``), each
followed by the label-assignment evaluation of
``repro.train.stdp_trainer``: every excitatory neuron is assigned to its
max-response class on a held-out pass, then samples classify by the
assigned-population vote — the fully unsupervised Table II protocol.

Run:  PYTHONPATH=src python examples/train_snn.py \
          [--net 2layer-snn|6layer-dcsnn|5layer-csnn] \
          [--rule itp|itp_nocomp|exact|linear|imstdp] \
          [--backend reference|fused|fused_interpret|sparse] \
          [--epochs 5] [--theta-plus 0.02] [--hard-wta]

Every flag is declared once in ``repro.launch.cli`` and shared verbatim
with ``python -m repro.launch.train --snn`` — the two entry points build
the same ``SNNConfig`` / ``TrainerConfig`` pair.  ``--rule`` selects the
learning rule from the ``repro.plasticity`` registry (the paper's
Table II comparison axis); every rule runs on every backend it supports,
so the accuracy comparison is kernel-vs-kernel.
"""
import argparse

from repro.launch import cli
from repro.launch.compile_cache import enable_compile_cache
from repro.models import snn
from repro.train.stdp_trainer import train_to_accuracy


def main():
    ap = argparse.ArgumentParser()
    cli.add_net_flag(ap, "--net")
    cli.add_update_flags(ap)
    cli.add_train_flags(ap)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = cli.snn_config_from_args(args)
    tcfg = cli.trainer_config_from_args(args)
    sampler, n_classes = cli.sampler_for(args.net)

    print(f"training {cfg.name} ({'×'.join(str(d) for d in cfg.input_shape)}"
          f"→{snn.feature_size(cfg)}) with rule={cfg.rule!r} "
          f"backend={cfg.backend!r}: {tcfg.epochs} epochs × "
          f"{tcfg.batches_per_epoch} batches × {tcfg.t_steps} steps "
          f"(θ+ {cfg.theta_plus}, hard WTA {cfg.hard_wta})")
    result = train_to_accuracy(cfg, sampler, n_classes, tcfg, verbose=True)
    print(f"STDP training done in {result['train_seconds']:.1f}s")
    print(f"assignment accuracy: {result['final_accuracy']:.3f} "
          f"(chance {result['chance']:.3f}) — net={cfg.name!r} "
          f"rule={cfg.rule!r} backend={cfg.backend!r}")


if __name__ == "__main__":
    main()
