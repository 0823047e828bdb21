"""What the timed path produced, against the plain reference.

The reference of each configuration (``configs/<reference>.py``) runs
one raster from rest on given weights.  Each compared raster starts the
reference from the weights the program held before it (the program's
first raster starts from weights the reference draws itself from the
seed, and those are compared first), so a knife-edge event in one
raster (a membrane potential on its threshold, a weight on a half
level) is compared where it happens and is not carried into the next.

Numbers, each the worst over the compared rasters:

  * ``counts_gap``: sum |program - reference| of the last layer's spike
    counts over the sum of the reference's;
  * ``row_gap``: the same for each sample alone; the worst sample (an
    answer that belongs to another sample reads about 1);
  * ``dw_gap``: per learnable layer, the gap between the norms of the
    program's and the reference's weight change over the raster, against
    the reference's norm of that layer or of the median layer, whichever
    is larger; the worst layer;
  * ``level_share``: per learnable layer, the share of weights on a
    different level of the quantisation grid; the worst layer;
  * ``hist_share``: per learnable layer, the share of spike-register bits
    (input and output registers after the raster) that differ; the worst
    layer;
  * exact ones (limit 0): ``raster_diff`` (spikes of the raster against
    the reference's own encoding of the same pool rows), ``init_w_diff``
    (the program's initial weights against the reference's draw),
    ``frozen_w_diff`` (inference: the program's weights after the window
    against the reference's draw).
"""
from __future__ import annotations

import importlib
import statistics

import jax
import jax.numpy as jnp
import numpy as np


def reference_module(c: dict):
    return importlib.import_module(f"configs.{c['reference']}")


def program_registers(hist) -> np.ndarray:
    """(depth, N) registers of a ring buffer, k = 0 the newest step."""
    planes = np.asarray(hist.planes)
    head = int(hist.head)
    depth = planes.shape[0]
    return planes[[(head - k) % depth for k in range(depth)]]


def encode(key: jax.Array, x: jax.Array, t_steps: int) -> jax.Array:
    """Per-sample min-max normalisation (eq. 28) and Bernoulli coding
    (eqs. 29-30): (B, ...) -> (T, B, features) uint8."""
    flat = x.reshape(x.shape[0], -1).astype(jnp.float32)
    lo = flat.min(axis=-1, keepdims=True)
    hi = flat.max(axis=-1, keepdims=True)
    norm = (flat - lo) / jnp.maximum(hi - lo, 1e-12)
    u = jax.random.uniform(key, (t_steps,) + norm.shape)
    return (u < norm[None]).astype(jnp.uint8)


def count_gaps(c_p, c_r) -> tuple[float, float]:
    """(all samples, worst sample) of sum |program - reference| over the
    sum of the reference's counts."""
    c_p = np.asarray(c_p, np.float64)
    c_r = np.asarray(c_r, np.float64)
    d = np.abs(c_p - c_r)
    rows = d.sum(axis=1) / np.maximum(c_r.sum(axis=1), 1.0)
    return float(d.sum() / max(c_r.sum(), 1.0)), float(rows.max())


def _norm(a) -> float:
    return float(np.linalg.norm(np.asarray(a, np.float64)))


def raster_numbers(rc, prog: dict, w_in: tuple, ref: tuple) -> dict:
    """The compared numbers of one training raster; ``w_in`` is what the
    reference started from."""
    ref_w, ref_counts, ref_layers = ref
    levels = (1 << (rc.w_bits - 1)) - 1
    d_p = [_norm(np.asarray(a) - np.asarray(b))
           for a, b in zip(prog["w_after"], prog["w_before"])]
    d_r = [_norm(np.asarray(a) - np.asarray(b)) for a, b in zip(ref_w, w_in)]
    floor = statistics.median(d_r)
    dw_gap = [abs(p - r) / max(r, floor, 1e-30) for p, r in zip(d_p, d_r)]
    level = [float(np.mean(np.round(np.asarray(a) * levels) != np.round(np.asarray(b) * levels)))
             for a, b in zip(prog["w_after"], ref_w)]
    hist = []
    learn = [ls for ls in prog["layers"] if getattr(ls, "pre_hist", None) is not None]
    for ls, rl in zip(learn, ref_layers):
        bits = 0
        n = 0
        for side in ("pre", "post"):
            pr = program_registers(getattr(ls, f"{side}_hist"))
            rr = np.asarray(rl[side]).reshape(pr.shape)
            bits += int(np.sum(pr != rr))
            n += pr.size
        hist.append(bits / n)
    counts_gap, row_gap = count_gaps(prog["counts"], ref_counts)
    return {
        "counts_gap": counts_gap,
        "row_gap": row_gap,
        "dw_gap": max(dw_gap),
        "level_share": max(level),
        "hist_share": max(hist),
        "layers": {"dw_gap": dw_gap, "level_share": level, "hist_share": hist},
        "dw_norms": d_r,
        "post_rates": [float(np.mean(np.asarray(rl["spikes"]))) / prog["spikes"].shape[0]
                       for rl in ref_layers],
    }


def max_abs_diff(a: tuple, b: tuple) -> float:
    """The largest |a - b| over the layers' weights."""
    return max(float(jnp.max(jnp.abs(jnp.asarray(x) - jnp.asarray(y))))
               for x, y in zip(a, b))


def training_numbers(c: dict, rc, records: list, stream_key, sampler,
                     init_key) -> tuple[dict, list]:
    """Worst numbers over the recorded rasters, and the per-raster ones."""
    ref_mod = reference_module(c)
    init = c["weight_init"]
    w_ref = ref_mod.init_weights(init_key, rc, init["low"], init["high"])
    out = {"raster_diff": 0.0,
           "init_w_diff": max_abs_diff(records[0]["w_before"], w_ref)}
    per = []
    keys = _stream_keys(stream_key, len(records))
    for i, (rec, (k_data, k_enc)) in enumerate(zip(records, keys)):
        x, _ = sampler(k_data, rec["spikes"].shape[1])
        mine = encode(k_enc, x, rec["spikes"].shape[0])
        out["raster_diff"] = max(out["raster_diff"],
                                 float(jnp.mean(mine != rec["spikes"])))
        w_in = tuple(rec["w_before"]) if i else w_ref
        ref = ref_mod.run_raster(rc, w_in, mine, train=True)
        per.append(raster_numbers(rc, rec, w_in, ref))
    for key in ("counts_gap", "row_gap", "dw_gap", "level_share", "hist_share"):
        out[key] = max(p[key] for p in per)
    return out, per


def _stream_keys(key, n):
    out = []
    for _ in range(n):
        key, k_data, k_enc = jax.random.split(key, 3)
        out.append((k_data, k_enc))
    return out


class _Registers:
    """A ring buffer holding given registers (head 0, k = 0 newest)."""

    def __init__(self, regs):
        regs = np.asarray(regs).reshape(regs.shape[0], -1)
        depth = regs.shape[0]
        self.planes = regs[[(-k) % depth for k in range(depth)]]
        self.head = 0


class _Layer:
    def __init__(self, ref_layer):
        self.pre_hist = _Registers(ref_layer["pre"])
        self.post_hist = _Registers(ref_layer["post"])


def reference_records(rc, w_init: tuple, rasters: list) -> list:
    """Records of the reference put in the program's place: it runs the
    rasters one after another from ``w_init`` on its own weights, and
    its outputs take the shape of the program's records."""
    ref_mod_records, w = [], tuple(w_init)
    run_raster = _run_raster_of(rc)
    for spikes in rasters:
        w_after, counts, layers = run_raster(rc, w, spikes, train=True)
        ref_mod_records.append({"spikes": spikes, "w_before": w, "w_after": w_after,
                                "counts": counts, "layers": [_Layer(l) for l in layers]})
        w = w_after
    return ref_mod_records


def _run_raster_of(rc):
    return importlib.import_module(type(rc).__module__).run_raster
