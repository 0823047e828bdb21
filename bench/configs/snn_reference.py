"""Plain reference of one training or inference raster of the paper's SNNs.

Straight ``jax.numpy`` of the step the configuration files in this
directory describe, written from the paper's equations and the stated
settings alone: it imports nothing of the program under test.

One raster is ``T`` steps over a batch; every step runs each layer in
order:

  * forward current ``I = gain * (patches @ W) / max(mean active inputs, 1)``,
    contracted at the precision the configuration states for the forward
    current (``"default"`` is one bf16 pass with f32 accumulation: the
    weights are rounded to bf16 and the products summed in f32);
  * soft lateral inhibition ``I -= inhibition * (sum of the layer's
    previous-step spikes in the sample - own previous spike)``;
  * LIF (eqs. 4-5) or Izhikevich (Euler, clamped) dynamics;
  * training only: nearest-neighbour ITP-STDP (eq. 20 compensated, so the
    magnitude of a pairing k steps back is ``A * exp(-k / tau)``), gated so
    that LTP needs the post neuron to fire alone and LTD the pre neuron,
    summed over batch and positions by two contractions at the stated
    update precision, scaled by ``eta / (B * P)``, clipped to [0, 1] and
    rounded onto the ``2**(w_bits-1) - 1`` level grid;
  * the step's input and output spikes enter the layer's depth-``depth``
    spike registers (k = 0 is the newest step).

Pooling is an OR over windows of ``pool`` steps (1-D) or ``pool x pool``
pixels (2-D), the remainder of the input left out.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
HIGH = jax.lax.Precision.HIGH

# the weights as a forward contraction of the named precision sees them:
# (exponent bits, mantissa bits) of the operand type, rounded to nearest
# even.  Spikes are {0, 1} and exact in every one of these types.
# "default" is one bf16 pass on a TPU and plain f32 on a CPU.
_OPERAND_BITS = {
    "highest": None,
    "bf16": (8, 7),
    "fp8": (4, 3),
}


def forward_operand(w: jax.Array, name: str) -> jax.Array:
    if name == "default":
        name = "bf16" if jax.default_backend() == "tpu" else "highest"
    bits = _OPERAND_BITS[name]
    if bits is None:
        return w
    return jax.lax.reduce_precision(w, exponent_bits=bits[0], mantissa_bits=bits[1])


_CONTRACT = {"highest": HIGHEST, "high": HIGH}


@dataclasses.dataclass(frozen=True)
class RefConfig:
    """The settings of one configuration file, as hashable statics."""

    input_shape: tuple
    layers: tuple          # ((kind, out_features, kernel, stride, pool), ...)
    neuron: str
    lif: tuple             # (tau, v_th, e_rest)
    izhi: tuple            # (a, b, c, d, v_th, dt)
    izhi_gain: float
    gain: float
    inhibition: float
    depth: int
    eta: float
    stdp: tuple            # (a_plus, a_minus, tau_plus, tau_minus)
    w_bits: int
    quantise: bool
    forward: str           # operand precision of the forward current
    update: str            # contraction precision of the STDP update

    @classmethod
    def from_file(cls, c: dict, *, forward: str | None = None,
                  update: str | None = None) -> "RefConfig":
        for key in ("hard_wta",):
            if c[key]:
                raise ValueError(f"the reference does not model {key}")
        if c["theta_plus"] != 0.0:
            raise ValueError("the reference does not model threshold homeostasis")
        if c["rule"] != "itp" or c["pairing"] != "nearest" or not c["compensate"]:
            raise ValueError("the reference models rule itp, nearest pairing, compensated")
        layers = tuple((l["kind"], l.get("out_features", 0), l.get("kernel", 0),
                        l.get("stride", 1), l.get("pool", 0)) for l in c["layers"])
        lif, iz, sp = c["lif"], c["izhi"], c["stdp"]
        prec = c["precision"]
        upd = prec["fc_update"]
        if any(l[0] in ("conv1d", "conv2d") for l in layers) and prec["conv_update"] != upd:
            raise ValueError("one update precision for all layers is modelled")
        return cls(
            input_shape=tuple(c["input_shape"]), layers=layers, neuron=c["neuron"],
            lif=(lif["tau"], lif["v_th"], lif["e_rest"]),
            izhi=(iz["a"], iz["b"], iz["c"], iz["d"], iz["v_th"], iz["dt"]),
            izhi_gain=c["izhi_gain"], gain=c["gain"], inhibition=c["inhibition"],
            depth=c["depth"], eta=c["eta"],
            stdp=(sp["a_plus"], sp["a_minus"], sp["tau_plus"], sp["tau_minus"]),
            w_bits=c["w_bits"], quantise=c["quantise"],
            forward=forward or prec["forward_current"], update=update or upd)


# ---------------------------------------------------------------------------
# shapes and initial weights
# ---------------------------------------------------------------------------

def is_pool(kind: str) -> bool:
    return kind in ("pool1d", "pool2d")


def layer_shapes(rc: RefConfig) -> list[tuple]:
    """(input shape, output shape) of every layer, batch excluded."""
    shape, out = rc.input_shape, []
    for kind, c_out, k, s, p in rc.layers:
        if kind == "fc":
            new = (c_out,)
        elif kind == "conv2d":
            h, w, _ = shape
            new = ((h - k) // s + 1, (w - k) // s + 1, c_out)
        elif kind == "conv1d":
            length, _ = shape
            new = ((length - k) // s + 1, c_out)
        elif kind == "pool2d":
            h, w, c = shape
            new = (h // p, w // p, c)
        elif kind == "pool1d":
            length, c = shape
            new = (length // p, c)
        else:
            raise ValueError(f"layer kind {kind!r} is not modelled")
        out.append((shape, new))
        shape = new
    return out


def fan_in(kind: str, k: int, in_shape: tuple) -> int:
    if kind == "fc":
        return math.prod(in_shape)
    if kind == "conv1d":
        return k * in_shape[-1]
    return k * k * in_shape[-1]


def init_weights(key: jax.Array, rc: RefConfig, low: float, high: float) -> tuple:
    """U(low, high) weights per learnable layer, one key split per layer."""
    ws = []
    for (kind, c_out, k, _, _), (in_shape, _) in zip(rc.layers, layer_shapes(rc)):
        if is_pool(kind):
            continue
        key, sub = jax.random.split(key)
        ws.append(jax.random.uniform(sub, (fan_in(kind, k, in_shape), c_out),
                                     minval=low, maxval=high).astype(jnp.float32))
    return tuple(ws)


# ---------------------------------------------------------------------------
# one step
# ---------------------------------------------------------------------------

def patches_2d(x: jax.Array, k: int, s: int) -> jax.Array:
    """(B, H, W, C) -> (B, Ho*Wo, k*k*C), features in (kh, kw, c) order."""
    B, H, W, C = x.shape
    ho, wo = (H - k) // s + 1, (W - k) // s + 1
    cols = [x[:, i:i + s * (ho - 1) + 1:s, j:j + s * (wo - 1) + 1:s, :]
            for i in range(k) for j in range(k)]
    return jnp.stack(cols, axis=3).reshape(B, ho * wo, k * k * C)


def patches_1d(x: jax.Array, k: int, s: int) -> jax.Array:
    """(B, L, C) -> (B, Lo, k*C), features in (k, c) order."""
    B, L, C = x.shape
    lo = (L - k) // s + 1
    cols = [x[:, i:i + s * (lo - 1) + 1:s, :] for i in range(k)]
    return jnp.stack(cols, axis=2).reshape(B, lo, k * C)


def _patches(kind: str, x: jax.Array, k: int, s: int) -> jax.Array:
    if kind == "fc":
        return x.reshape(x.shape[0], 1, -1)
    if kind == "conv1d":
        return patches_1d(x, k, s)
    return patches_2d(x, k, s)


def nearest_magnitude(reg: jax.Array, amplitude: float, tau: float) -> jax.Array:
    """A * exp(-k*/tau) of the newest spike k* in each register, else 0.

    ``reg`` is (depth, ...) {0,1}, k = 0 the newest step.
    """
    depth = reg.shape[0]
    table = amplitude * jnp.exp(-jnp.arange(depth, dtype=jnp.float32) / tau)
    any_spike = jnp.any(reg > 0, axis=0)
    k_star = jnp.argmax(reg > 0, axis=0)
    return jnp.where(any_spike, table[k_star], 0.0)


def _push(reg: jax.Array, spikes: jax.Array) -> jax.Array:
    return jnp.concatenate([spikes[None].astype(reg.dtype), reg[:-1]], axis=0)


def _quantise(w: jax.Array, bits: int) -> jax.Array:
    levels = (1 << (bits - 1)) - 1
    return jnp.round(w * levels) / levels


def _dynamics(rc: RefConfig, nstate: tuple, i_in: jax.Array):
    if rc.neuron == "lif":
        tau, v_th, e = rc.lif
        (v,) = nstate
        v = math.exp(-1.0 / tau) * (v - e) + e + i_in
        spikes = v > v_th
        return (jnp.where(spikes, e, v),), spikes
    a, b, c, d, v_th, dt = rc.izhi
    v, u = nstate
    i_in = rc.izhi_gain * i_in
    dv = 0.04 * v * v + 5.0 * v + 140.0 - u + i_in
    du = a * (b * v - u)
    v = v + dt * dv
    u = u + dt * du
    spikes = v >= v_th
    v = jnp.where(spikes, c, v)
    u = jnp.where(spikes, u + d, u)
    return (jnp.clip(v, -120.0, v_th), u), spikes


def _fresh_neurons(rc: RefConfig, shape: tuple) -> tuple:
    if rc.neuron == "lif":
        return (jnp.full(shape, rc.lif[2], jnp.float32),)
    c, b = rc.izhi[2], rc.izhi[1]
    v = jnp.full(shape, c, jnp.float32)
    return (v, b * v)


def learnable_step(rc: RefConfig, spec: tuple, w: jax.Array, ls: dict,
                   s_in: jax.Array, train: bool):
    """One step of an fc/conv layer; returns (w', layer state', spikes)."""
    kind, _, k, s, _ = spec
    B = s_in.shape[0]
    x = s_in.astype(jnp.float32)
    pat = _patches(kind, x, k, s)                                   # (B, P, K)
    w_op = forward_operand(w, rc.forward)
    act = jnp.mean(jnp.sum(pat, axis=-1))
    i_in = rc.gain * jnp.einsum("bpk,kc->bpc", pat, w_op, precision=HIGHEST) \
        / jnp.maximum(act, 1.0)
    i_in = i_in.reshape((B,) + ls["post"].shape[2:])
    if rc.inhibition > 0.0:
        prev = ls["post"][0].astype(jnp.float32)
        i_in = i_in - rc.inhibition * (jnp.sum(prev, axis=-1, keepdims=True) - prev)
    neurons, spikes = _dynamics(rc, ls["neurons"], i_in)
    y = spikes.astype(jnp.float32)
    if train:
        a_p, a_m, t_p, t_m = rc.stdp
        ltp = _patches(kind, nearest_magnitude(ls["pre"], a_p, t_p), k, s)
        ltd = nearest_magnitude(ls["post"], a_m, t_m).reshape(B, -1, w.shape[1])
        post = y.reshape(B, -1, w.shape[1])
        prec = _CONTRACT[rc.update]
        dw = jnp.einsum("bpk,bpc->kc", (1.0 - pat) * ltp, post, precision=prec) \
            - jnp.einsum("bpk,bpc->kc", pat, (1.0 - post) * ltd, precision=prec)
        w = jnp.clip(w + rc.eta * dw / float(B * pat.shape[1]), 0.0, 1.0)
        if rc.quantise:
            w = _quantise(w, rc.w_bits)
    ls = {"neurons": neurons, "pre": _push(ls["pre"], s_in),
          "post": _push(ls["post"], spikes), "spikes": ls["spikes"] + y}
    return w, ls, spikes


def _pool(kind: str, x: jax.Array, p: int) -> jax.Array:
    if kind == "pool1d":
        B, L, C = x.shape
        x = x[:, :L // p * p].astype(jnp.float32)
        return x.reshape(B, L // p, p, C).max(axis=2) > 0.5
    B, H, W, C = x.shape
    x = x[:, :H // p * p, :W // p * p].astype(jnp.float32)
    return x.reshape(B, H // p, p, W // p, p, C).max(axis=(2, 4)) > 0.5


def fresh_state(rc: RefConfig, batch: int) -> list:
    """Per learnable layer: rest-state neurons, empty registers, no spikes."""
    out = []
    for (kind, *_), (in_shape, o_shape) in zip(rc.layers, layer_shapes(rc)):
        if is_pool(kind):
            continue
        out.append({
            "neurons": _fresh_neurons(rc, (batch,) + o_shape),
            "pre": jnp.zeros((rc.depth, batch) + in_shape, jnp.uint8),
            "post": jnp.zeros((rc.depth, batch) + o_shape, jnp.uint8),
            "spikes": jnp.zeros((batch,) + o_shape, jnp.float32),
        })
    return out


@partial(jax.jit, static_argnames=("rc", "train"))
def run_raster(rc: RefConfig, weights: tuple, raster: jax.Array, *, train: bool):
    """One raster (T, B, features) from rest; weights in, weights out.

    Returns ``(weights', counts, layers)``: ``counts`` is the last layer's
    (B, features) spike count, ``layers`` per learnable layer its final
    registers ``pre``/``post`` (depth, B, ...) and ``spikes`` (its spike
    count per neuron over the raster).
    """
    T, B = raster.shape[:2]
    x = raster.reshape((T, B) + rc.input_shape)

    def step(carry, xt):
        ws, layers = carry
        new_w, new_l = [], []
        s, li = xt, 0
        for spec in rc.layers:
            if is_pool(spec[0]):
                s = _pool(spec[0], s, spec[4])
                continue
            w, ls, s = learnable_step(rc, spec, ws[li], layers[li], s, train)
            new_w.append(w)
            new_l.append(ls)
            li += 1
        return (tuple(new_w), new_l), s.reshape(B, -1).astype(jnp.float32)

    (weights, layers), outs = jax.lax.scan(step, (tuple(weights), fresh_state(rc, B)), x)
    return weights, outs.sum(axis=0), layers
