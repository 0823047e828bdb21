"""Operations and bytes that one simulation step needs, from logical shapes.

Every count is a function of a learnable layer's logical sizes: batch
``B``, output positions ``P`` (1 for fc), patch width ``K`` (fan-in),
output channels ``C``, and the input plane ``N_in`` (elements of the
layer's input per sample, before any patch gather).  Lane padding,
im2col copies and per-sample delta tiles are not counted: a change that
removes them moves the same work in less time.

  forward current     2 B P K C              (one multiply-add per synapse use)
  STDP update         4 B P K C              (two pair-gated contractions)
  update bytes        input spikes and words: 2 B N_in   (1 byte each)
                      output spikes and words: 2 B P C   (1 byte each)
                      the (K, C) f32 delta:   4 K C
"""
from __future__ import annotations

import math


def layers(c: dict, batch: int) -> list[dict]:
    """The logical sizes of each learnable layer of configuration ``c``."""
    shape, out = tuple(c["input_shape"]), []
    for spec in c["layers"]:
        kind = spec["kind"]
        if kind == "pool2d":
            p = spec["pool"]
            shape = (shape[0] // p, shape[1] // p, shape[2])
            continue
        if kind == "pool1d":
            shape = (shape[0] // spec["pool"], shape[1])
            continue
        n_in = math.prod(shape)
        if kind == "fc":
            P, K, new = 1, n_in, (spec["out_features"],)
        elif kind == "conv2d":
            k, s = spec["kernel"], spec.get("stride", 1)
            ho, wo = (shape[0] - k) // s + 1, (shape[1] - k) // s + 1
            P, K, new = ho * wo, k * k * shape[2], (ho, wo, spec["out_features"])
        elif kind == "conv1d":
            k, s = spec["kernel"], spec.get("stride", 1)
            lo = (shape[0] - k) // s + 1
            P, K, new = lo, k * shape[1], (lo, spec["out_features"])
        else:
            raise ValueError(f"no cost model for layer kind {kind!r}")
        out.append({"kind": kind, "B": batch, "P": P, "K": K,
                    "C": spec["out_features"], "N_in": n_in})
        shape = new
    return out


def forward_flops(l: dict) -> float:
    return 2.0 * l["B"] * l["P"] * l["K"] * l["C"]


def update_flops(l: dict) -> float:
    return 4.0 * l["B"] * l["P"] * l["K"] * l["C"]


def update_bytes(l: dict) -> float:
    return 2.0 * l["B"] * l["N_in"] + 2.0 * l["B"] * l["P"] * l["C"] + 4.0 * l["K"] * l["C"]


def step_flops(c: dict, batch: int, train: bool) -> float:
    """Forward (and, training, update) operations of one step, all layers."""
    return sum(forward_flops(l) + (update_flops(l) if train else 0.0)
               for l in layers(c, batch))


def update_min_seconds(ls: list[dict], peak: dict) -> tuple[float, str]:
    """Least time the updates of ``ls`` can take on a chip, and its bound."""
    t_flops = sum(update_flops(l) for l in ls) / peak["bf16_flops_per_s"]
    t_bytes = sum(update_bytes(l) for l in ls) / peak["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")
