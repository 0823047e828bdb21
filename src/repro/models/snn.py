"""The paper's three validation networks (§IV-C, Table II):

  * 2-layer SNN   — LIF neurons, fully connected, MNIST-class data
  * 6-layer DCSNN — Izhikevich neurons, conv stack, Fashion-MNIST-class data
  * 5-layer CSNN  — LIF neurons, 1-D conv stack, motor-fault time series

All layers learn with a selectable learning rule from the
``repro.plasticity`` registry ('itp' / 'itp_nocomp' history rules, 'exact'
/ 'linear' / 'imstdp' counter rules), sharing one protocol so the Table II
*parity* comparison is apples-to-apples.  Convolutional STDP applies the
pair-based rule per (patch-pixel → output-neuron) synapse, accumulated over
spatial positions at the patch level (the dense layer is the 1×1 special
case): every rule × backend cell dispatches through the plasticity apply
layer (``repro.plasticity.apply`` — conv layers via ``UpdatePlan.
conv_delta``, fc layers via ``UpdatePlan.fc_delta``), which routes to the
rule's im2col-fused kernel package (``repro.kernels.itp_stdp_conv`` for
the history rules, ``repro.kernels.itp_counter`` for the counter rules),
its dense engine kernel, its event-driven path, or its pure-jnp oracle —
so the full rule × backend matrix runs end-to-end at the network level.  Readout is a deterministic ridge
regression on time-averaged spike counts — identical across rules, so
accuracy differences isolate the learning rule.

For the history rules, weight-update magnitudes come from the same
bitplane histories as the learning engine: ``itp`` reads the history
against e^(-k/τ) ≡ 2^(-k/(τ·ln2)) (identical by eq. 18 — the paper's
equivalence), ``itp_nocomp`` against the raw po2 place values 2^(-k/τ).
The counter rule ``exact`` evaluates e^(-Δt/τ) from last-spike counters —
trajectory-identical to compensated ``itp`` on the integer grid, which is
exactly the paper's equivalence claim.
"""
from __future__ import annotations

import dataclasses
import math
from functools import lru_cache, partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation, annotate_function

from repro import plasticity, tracing
from repro.core.lif import (IzhikevichParams, LIFParams, izhikevich_init,
                            izhikevich_step, lif_init, lif_step)
from repro.core.stdp import STDPParams
from repro.kernels.dispatch import im2col_1d, im2col_2d, resolve_packed


# ---------------------------------------------------------------------------
# Config
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SNNLayerSpec:
    kind: str                      # "fc" | "conv2d" | "conv1d" | "pool2d" | "pool1d"
    out_features: int = 0          # fc width / conv out-channels
    kernel: int = 3
    stride: int = 1
    pool: int = 2


@dataclasses.dataclass(frozen=True)
class SNNConfig:
    name: str
    input_shape: tuple            # (H, W, C) images / (L, C) series / (N,) flat
    layers: tuple                 # tuple[SNNLayerSpec, ...]
    neuron: str = "lif"           # lif | izhikevich
    rule: str = "itp"             # plasticity.rule_names()
    depth: int = 7                # spike-history depth (§IV-B)
    pairing: str = "nearest"
    eta: float = 1.0 / 64.0
    gain: float = 4.0             # synaptic gain / fan-in normalisation
    izhi_gain: float = 20.0       # current scale into the Izhikevich model
    w_bits: int = 8
    quantise: bool = True
    backend: str = "reference"    # reference | fused | fused_interpret
                                  # | sparse (event-driven)
    max_events: int | None = None  # sparse backend: static event-list cap
                                  # per side and per sample (None = popu-
                                  # lation size; excess events beyond the
                                  # cap are deterministically the highest-
                                  # indexed and are dropped)
    packed_history: bool = True   # fused* datapaths read packed uint8
                                  # register words (one byte per neuron /
                                  # patch element); False keeps the unpacked
                                  # bitplane kernel operands (the oracle)
    inhibition: float = 0.0       # lateral inhibition strength (2-layer SNN)
    hard_wta: bool = False        # hard winner-take-all: per sample (and
                                  # spatial position) only the most-driven
                                  # super-threshold neuron fires; the
                                  # suppressed ones are shunt-inhibited
                                  # (membrane reset).  Stacks on top of the
                                  # soft `inhibition` current.
    theta_plus: float = 0.0       # adaptive-threshold homeostasis: per-
                                  # neuron threshold increment per spike
                                  # (0 disables; θ is per output channel,
                                  # persists across sample resets)
    theta_tau: float = 200.0      # θ decay time constant (steps)
    stdp: STDPParams = dataclasses.field(default_factory=STDPParams)
    lif: LIFParams = dataclasses.field(
        default_factory=lambda: LIFParams(tau=2.0, v_th=0.6))
    izhi: IzhikevichParams = dataclasses.field(default_factory=IzhikevichParams)

    def __post_init__(self):
        # config-construction-time validation of the rule × backend cell —
        # the single shared validator (plasticity.validate_update_config)
        # keeps messages and valid-option listings identical to
        # EngineConfig's — plus the SNN-only homeostasis knobs
        plasticity.validate_update_config(
            rule=self.rule, backend=self.backend, pairing=self.pairing,
            max_events=self.max_events)
        if self.theta_plus < 0.0:
            raise ValueError(
                f"theta_plus must be >= 0 (0 disables homeostasis), "
                f"got {self.theta_plus}")
        if self.theta_tau <= 0.0:
            raise ValueError(
                f"theta_tau must be a positive decay time constant "
                f"(steps), got {self.theta_tau}")

    def learning_rule(self) -> plasticity.LearningRule:
        return plasticity.get_rule(self.rule)

    @property
    def compensate(self) -> bool:
        # 'exact' and compensated 'itp' are numerically identical on the
        # integer delay grid (paper eq. 18) — both read e^(-k/τ);
        # 'itp_nocomp' pins the raw po2 read via its rule override.
        rc = self.learning_rule().compensate
        return True if rc is None else rc

    def use_packed_history(self) -> bool:
        """Packed uint8 words hold depth <= 8 only; deeper histories keep
        the unpacked bitplane kernel operands (bit-identical, so packing
        is purely a bandwidth optimisation — never a trace-time failure).
        Resolution is owned by ``repro.kernels.dispatch.resolve_packed``."""
        return resolve_packed(self.packed_history, depth=self.depth)


# The paper's three networks -------------------------------------------------

def mnist_2layer(rule: str = "itp", n_hidden: int = 100, **kw) -> SNNConfig:
    """2-layer fully connected SNN (LIF) for MNIST-class images."""
    return SNNConfig(
        name="2layer-snn",
        input_shape=(28, 28, 1),
        layers=(SNNLayerSpec("fc", out_features=n_hidden),),
        neuron="lif", rule=rule, inhibition=0.1, gain=1.2, **kw)


def fmnist_dcsnn(rule: str = "itp", **kw) -> SNNConfig:
    """6-layer deep convolutional SNN (Izhikevich) for Fashion-MNIST-class
    images: conv-pool-conv-pool-fc-readout (readout is external)."""
    return SNNConfig(
        name="6layer-dcsnn",
        input_shape=(28, 28, 1),
        layers=(
            SNNLayerSpec("conv2d", out_features=12, kernel=5),
            SNNLayerSpec("pool2d", pool=2),
            SNNLayerSpec("conv2d", out_features=24, kernel=3),
            SNNLayerSpec("pool2d", pool=2),
            SNNLayerSpec("fc", out_features=128),
        ),
        neuron="izhikevich", rule=rule, gain=1.2,
        izhi=IzhikevichParams(dt=0.5), **kw)


def fault_csnn(rule: str = "itp", length: int = 512, channels: int = 2,
               **kw) -> SNNConfig:
    """5-layer 1-D convolutional SNN (LIF) for motor-fault time series."""
    return SNNConfig(
        name="5layer-csnn",
        input_shape=(length, channels),
        layers=(
            SNNLayerSpec("conv1d", out_features=8, kernel=7, stride=2),
            SNNLayerSpec("pool1d", pool=2),
            SNNLayerSpec("conv1d", out_features=16, kernel=5, stride=2),
            SNNLayerSpec("pool1d", pool=2),
            SNNLayerSpec("fc", out_features=64),
        ),
        neuron="lif", rule=rule, gain=1.2,
        lif=LIFParams(tau=2.0, v_th=0.8), **kw)


PAPER_NETWORKS = {
    "2layer-snn": mnist_2layer,
    "6layer-dcsnn": fmnist_dcsnn,
    "5layer-csnn": fault_csnn,
}


# ---------------------------------------------------------------------------
# Layer shape inference
# ---------------------------------------------------------------------------

def _layer_shapes(cfg: SNNConfig) -> list[tuple]:
    """Output feature shape after each layer (excluding batch)."""
    shape = tuple(cfg.input_shape)
    out = []
    for spec in cfg.layers:
        if spec.kind == "fc":
            shape = (spec.out_features,)
        elif spec.kind == "conv2d":
            h, w, _ = shape
            ho = (h - spec.kernel) // spec.stride + 1
            wo = (w - spec.kernel) // spec.stride + 1
            shape = (ho, wo, spec.out_features)
        elif spec.kind == "conv1d":
            l, _ = shape
            lo = (l - spec.kernel) // spec.stride + 1
            shape = (lo, spec.out_features)
        elif spec.kind == "pool2d":
            h, w, c = shape
            shape = (h // spec.pool, w // spec.pool, c)
        elif spec.kind == "pool1d":
            l, c = shape
            shape = (l // spec.pool, c)
        else:
            raise ValueError(spec.kind)
        out.append(shape)
    return out


def feature_size(cfg: SNNConfig) -> int:
    last = _layer_shapes(cfg)[-1]
    n = 1
    for d in last:
        n *= d
    return n


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

class LayerState(NamedTuple):
    neurons: Any                 # LIFState | IzhikevichState | None (pool)
    pre_hist: Any                # rule timing state (histories / counters)
    post_hist: Any
    theta: Any = None            # adaptive-threshold homeostasis state:
                                 # (out_features,) f32 per output channel
                                 # (None for pool layers).  Persists across
                                 # reset_dynamics — it is the slow
                                 # homeostatic variable, not fast dynamics.


class SNNState(NamedTuple):
    weights: tuple               # per learnable layer: (fan_in, out) f32
    layers: tuple                # per layer: LayerState


def _neuron_init(cfg: SNNConfig, shape) -> Any:
    if cfg.neuron == "izhikevich":
        return izhikevich_init(shape, cfg.izhi)
    return lif_init(shape, cfg.lif)


def _fan_in(spec: SNNLayerSpec, in_shape: tuple) -> int:
    if spec.kind == "fc":
        n = 1
        for d in in_shape:
            n *= d
        return n
    if spec.kind == "conv2d":
        return spec.kernel * spec.kernel * in_shape[-1]
    if spec.kind == "conv1d":
        return spec.kernel * in_shape[-1]
    return 0


def fresh_layers(cfg: SNNConfig, batch: int) -> tuple:
    """Each layer's state at the start of a raster: fresh neurons, the
    rule's empty timing state for both sides, zero θ (pool layers hold
    ``None``).  A function of ``(cfg, batch)`` alone: no PRNG, no weights,
    and the shape products come from Python, so nothing is read back from
    the device."""
    rule = cfg.learning_rule()
    states = []
    in_shape = tuple(cfg.input_shape)
    for spec, out_shape in zip(cfg.layers, _layer_shapes(cfg)):
        if spec.kind.startswith("pool"):
            states.append(LayerState(None, None, None))
        else:
            states.append(LayerState(
                neurons=_neuron_init(cfg, (batch,) + out_shape),
                pre_hist=rule.init_state(batch * math.prod(in_shape), cfg.depth),
                post_hist=rule.init_state(batch * math.prod(out_shape), cfg.depth),
                theta=jnp.zeros((spec.out_features,), jnp.float32),
            ))
        in_shape = out_shape
    return tuple(states)


@partial(annotate_function, name=tracing.INIT_SNN)
def init_snn(key: jax.Array, cfg: SNNConfig, batch: int) -> SNNState:
    in_shapes = [tuple(cfg.input_shape)] + _layer_shapes(cfg)[:-1]
    weights = []
    for spec, in_shape in zip(cfg.layers, in_shapes):
        if not spec.kind.startswith("pool"):
            key, sub = jax.random.split(key)
            w = jax.random.uniform(sub, (_fan_in(spec, in_shape), spec.out_features),
                                   minval=0.2, maxval=0.8)
            weights.append(w.astype(jnp.float32))
    return SNNState(weights=tuple(weights), layers=fresh_layers(cfg, batch))


def _quantise(w: jax.Array, cfg: SNNConfig) -> jax.Array:
    if not cfg.quantise:
        return w
    levels = (1 << (cfg.w_bits - 1)) - 1
    return jnp.round(w * levels) / levels


# ---------------------------------------------------------------------------
# Layer steps
# ---------------------------------------------------------------------------

def _learnable_step(spec: SNNLayerSpec, cfg: SNNConfig, w: jax.Array,
                    st: LayerState, spikes_in: jax.Array,
                    train: bool) -> tuple[jax.Array, LayerState, jax.Array]:
    """One step of an fc/conv STDP layer.

    spikes_in: (B, *in_shape) {0,1}.  Returns (w', state', spikes_out).
    """
    B = spikes_in.shape[0]
    s_in = spikes_in.astype(jnp.float32)

    # --- patches + synaptic accumulation --------------------------------
    if spec.kind == "fc":
        patches = s_in.reshape(B, 1, -1)                   # (B, P=1, fan_in)
    elif spec.kind == "conv2d":
        p = im2col_2d(s_in, spec.kernel, spec.stride)      # (B,Ho,Wo,K)
        patches = p.reshape(B, -1, p.shape[-1])
        out_hw = p.shape[1:3]
    else:                                                   # conv1d
        p = im2col_1d(s_in, spec.kernel, spec.stride)
        patches = p.reshape(B, -1, p.shape[-1])
        out_l = p.shape[1]
    # activity-normalised accumulation: scale by the *population mean*
    # active-synapse count (a per-step scalar), which keeps the layer's
    # operating point invariant to width/sparsity (synaptic-scaling
    # homeostasis) while preserving within-step selectivity — patches
    # with stronger weighted input still drive proportionally more
    # current, unlike a per-patch normaliser which flattens selectivity
    act_mean = jnp.mean(jnp.sum(patches, axis=-1))          # scalar
    i_in = cfg.gain * jnp.einsum("bpk,kc->bpc", patches, w) \
        / jnp.maximum(act_mean, 1.0)

    # --- lateral inhibition (2-layer SNN soft WTA) -----------------------
    if cfg.inhibition > 0.0 and st.post_hist is not None:
        prev = cfg.learning_rule().last_spikes(st.post_hist)
        prev = prev.reshape(i_in.shape[0], -1).reshape(i_in.shape)
        total = jnp.sum(prev, axis=-1, keepdims=True)
        i_in = i_in - cfg.inhibition * (total - prev)

    # --- neuron dynamics --------------------------------------------------
    if spec.kind == "fc":
        out_shape = (B, w.shape[1])
    elif spec.kind == "conv2d":
        out_shape = (B,) + out_hw + (w.shape[1],)
    else:
        out_shape = (B, out_l, w.shape[1])
    i_flat = i_in.reshape(out_shape)
    # adaptive-threshold homeostasis: the per-output-channel θ raises each
    # neuron's effective threshold, equalising firing rates so no subset of
    # neurons captures every input (θ stays all-zero when theta_plus == 0,
    # leaving the classic fixed-threshold trajectories untouched)
    theta = st.theta if st.theta is not None else 0.0
    if cfg.neuron == "izhikevich":
        neurons, spikes_out = izhikevich_step(
            st.neurons, cfg.izhi_gain * i_flat, cfg.izhi, v_th_offset=theta)
    else:
        neurons, spikes_out = lif_step(st.neurons, i_flat, cfg.lif,
                                       v_th_offset=theta)
    if cfg.hard_wta:
        # hard WTA on top of the soft inhibition current: per sample (and
        # spatial position) only the most-driven super-threshold neuron
        # keeps its spike; the suppressed ones were already membrane-reset
        # in the neuron step (shunt-inhibition semantics)
        drive = jnp.where(spikes_out, i_flat, -jnp.inf)
        winner = jnp.argmax(drive, axis=-1)[..., None]
        spikes_out = spikes_out & (jnp.arange(i_flat.shape[-1]) == winner)
    s_out = spikes_out.astype(jnp.float32)

    # --- STDP update (dispatched through the plasticity apply layer) ------
    # One UpdatePlan owns backend resolution, packed-readout selection and
    # the fused / event-driven / reference delta variants for both layer
    # kinds (repro.plasticity.apply); the layer keeps only model-level
    # policy — batch/patch-position normalisation, the fixed [0, 1] weight
    # window, and quantisation.
    rule = cfg.learning_rule()
    if train:
        plan = plasticity.make_plan(cfg)
        with jax.named_scope(tracing.UPDATE):
            if spec.kind != "fc":
                dw = plan.conv_delta(st.pre_hist, st.post_hist, patches, s_out,
                                     in_shape=spikes_in.shape[1:],
                                     kind=spec.kind, kernel=spec.kernel,
                                     stride=spec.stride)
            else:
                dw = plan.fc_delta(st.pre_hist, st.post_hist, s_in, s_out)
            denom = float(B * patches.shape[1])            # P = 1 for fc
            w = jnp.clip(w + cfg.eta * dw / denom, 0.0, 1.0)
            w = _quantise(w, cfg)

    # --- homeostasis θ update (training only; frozen during eval) ---------
    theta_new = st.theta
    if train and cfg.theta_plus > 0.0 and st.theta is not None:
        # exponential decay towards 0 plus an increment proportional to
        # each channel's firing rate this step (mean over batch + spatial
        # positions, so the operating point is batch-size invariant)
        rate = s_out.reshape(-1, s_out.shape[-1]).mean(axis=0)
        theta_new = st.theta * jnp.exp(-1.0 / cfg.theta_tau) \
            + cfg.theta_plus * rate

    # --- record new spikes (history shift-in / counter reset) ------------
    with jax.named_scope(tracing.TIMING):
        pre_hist = rule.step(st.pre_hist, s_in.reshape(-1), depth=cfg.depth)
        post_hist = rule.step(st.post_hist, s_out.reshape(-1), depth=cfg.depth)
    st = LayerState(neurons=neurons, pre_hist=pre_hist, post_hist=post_hist,
                    theta=theta_new)
    return w, st, spikes_out


def _pool_step(spec: SNNLayerSpec, spikes_in: jax.Array) -> jax.Array:
    """Spike OR-pooling (any spike in the window fires the pooled unit)."""
    s = spikes_in.astype(jnp.float32)
    if spec.kind == "pool2d":
        B, H, W, C = s.shape
        p = spec.pool
        s = s[:, :H // p * p, :W // p * p]
        s = s.reshape(B, H // p, p, W // p, p, C).max(axis=(2, 4))
    else:
        B, L, C = s.shape
        p = spec.pool
        s = s[:, :L // p * p]
        s = s.reshape(B, L // p, p, C).max(axis=2)
    return s > 0.5


# ---------------------------------------------------------------------------
# Network step / run
# ---------------------------------------------------------------------------

def snn_step(state: SNNState, spikes_in: jax.Array, cfg: SNNConfig,
             *, train: bool = True) -> tuple[SNNState, jax.Array]:
    """One simulation step through the whole stack; returns last-layer spikes."""
    new_w, new_l = [], []
    wi = 0
    s = spikes_in
    # every op of the step is forward work but those under the update and
    # timing scopes that _learnable_step opens inside this one
    with jax.named_scope(tracing.FORWARD):
        for spec, lst in zip(cfg.layers, state.layers):
            if spec.kind.startswith("pool"):
                s = _pool_step(spec, s)
                new_l.append(lst)
            else:
                w, lst2, s = _learnable_step(spec, cfg, state.weights[wi], lst,
                                             s, train)
                new_w.append(w)
                new_l.append(lst2)
                wi += 1
    return SNNState(weights=tuple(new_w), layers=tuple(new_l)), s


@partial(jax.jit, static_argnames=("cfg", "train"))
def run_snn(state: SNNState, raster: jax.Array, cfg: SNNConfig,
            *, train: bool = True) -> tuple[SNNState, jax.Array]:
    """Scan over a (T, B, *input_shape) raster.

    Returns (state', spike counts of the last layer (B, feature_size)).
    """
    T, B = raster.shape[:2]
    x = raster.reshape((T, B) + tuple(cfg.input_shape))

    def step(st, xt):
        st2, s_out = snn_step(st, xt, cfg, train=train)
        return st2, s_out.reshape(B, -1).astype(jnp.float32)

    state, outs = jax.lax.scan(step, state, x)
    return state, outs.sum(axis=0)


@lru_cache(maxsize=8)
def _reset_layers(cfg: SNNConfig, batch: int) -> tuple:
    # One immutable fresh state per (cfg, batch), shared by every reset.
    # Sound because nothing donates a state's buffers (run_snn does not), so
    # the arrays here are never deleted under the cache.  Built eagerly even
    # when the first reset runs under a trace, so the cache holds no tracers.
    with TraceAnnotation(tracing.FRESH_STATE), jax.ensure_compile_time_eval():
        return fresh_layers(cfg, batch)


@partial(annotate_function, name=tracing.RESET_DYNAMICS)
def reset_dynamics(state: SNNState, cfg: SNNConfig, batch: int) -> SNNState:
    """Zero neuron states + histories between samples; keep learned weights
    AND the adaptive thresholds θ — homeostasis is the slow variable that
    must integrate firing rates across samples, not within one raster.

    After the first call for a ``(cfg, batch)`` this launches nothing on
    the device and reads nothing back from it."""
    layers = tuple(
        f._replace(theta=old.theta) if old.theta is not None else f
        for f, old in zip(_reset_layers(cfg, batch), state.layers))
    return SNNState(weights=state.weights, layers=layers)


# ---------------------------------------------------------------------------
# Readout: ridge regression on spike counts (shared protocol, Table II)
# ---------------------------------------------------------------------------

def fit_readout(features: jax.Array, labels: jax.Array, n_classes: int,
                l2: float = 1e-3) -> jax.Array:
    """Closed-form ridge readout W: features (N, F) → one-hot labels."""
    X = jnp.asarray(features, jnp.float32)
    X = X / jnp.maximum(X.max(), 1.0)
    X = jnp.concatenate([X, jnp.ones((X.shape[0], 1))], axis=1)
    Y = jax.nn.one_hot(labels, n_classes)
    A = X.T @ X + l2 * jnp.eye(X.shape[1])
    return jnp.linalg.solve(A, X.T @ Y)


def readout_accuracy(W: jax.Array, features: jax.Array,
                     labels: jax.Array) -> float:
    X = jnp.asarray(features, jnp.float32)
    X = X / jnp.maximum(X.max(), 1.0)
    X = jnp.concatenate([X, jnp.ones((X.shape[0], 1))], axis=1)
    pred = jnp.argmax(X @ W, axis=-1)
    return float(jnp.mean(pred == labels))
