"""Public jit'd wrappers for the fused ITP-STDP kernel.

Bridges ``repro.core`` state (SpikeHistory ring buffers, STDPParams) to the
raw Pallas kernels, padding neuron counts to lane multiples.  Two datapath
variants share one set of entry points:

  * **packed** (the storage format the fused datapath runs on): one uint8
    history word per neuron (``repro.core.history.pack_words``, the
    hardware register file), unpacked to bitplanes in-register inside the
    kernel — ``weight_update_packed`` / ``synapse_delta_packed``;
  * **unpacked bitplane** (the oracle the packed path is pinned against):
    depth-major ``(depth, N)`` float32 registers —
    ``weight_update_depth_major`` / ``engine_weight_update`` /
    ``synapse_delta``.

``interpret`` defaults to ``None`` = "derive from the host via
``repro.kernels.dispatch.default_interpret``": compiled on accelerators,
interpreter only where nothing else runs (CPU) — selecting the fused
kernel can never silently mean interpreter mode on real hardware.

``BACKENDS`` / :func:`resolve_backend` (the canonical datapath selections
shared by ``EngineConfig.backend`` / ``SNNConfig.backend``) live in
``repro.kernels.dispatch`` and are re-exported here for back-compat.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.history import SpikeHistory, pack_words, registers_depth_major
from repro.core.stdp import STDPParams, po2_weights
from repro.kernels.dispatch import BACKENDS, LANE, SUBLANE, resolve_backend  # noqa: F401 (re-export)
from repro.kernels.dispatch import default_interpret, resolve_packed
from repro.kernels.dispatch import pad_axis as _pad_to
from repro.kernels.dispatch import round_up as _round_up
from repro.kernels.itp_stdp.kernel import (itp_stdp_update,
                                           itp_stdp_update_packed)
from repro.kernels.itp_stdp.ref import itp_stdp_update_ref


def _tile(padded: int) -> int:
    """Largest of (256, LANE) that divides the padded (LANE-multiple) dim."""
    return 256 if padded % 256 == 0 else LANE


# batch rows per grid step of the fused kernels (the reduction axis)
TILE_BATCH = 128


def _batch_tile(batch: int) -> tuple[int, int]:
    """``(tile_batch, padded batch)`` for a batch of ``batch`` rows.

    One row (the engine's rank-1 update) is a whole-array block and needs
    no padding; a larger batch pads to sublane rows, then to a multiple of
    :data:`TILE_BATCH`.  Zero rows carry no spikes and no history bits, so
    the padding adds nothing to Δw.
    """
    rows = batch if batch == 1 else _round_up(batch, SUBLANE)
    tb = min(TILE_BATCH, rows)
    return tb, _round_up(rows, tb)


def _resolve_interpret(interpret: bool | None) -> bool:
    return default_interpret() if interpret is None else interpret


def _read_vectors(params: STDPParams, depth: int, compensate: bool):
    po2_ltp = params.a_plus * po2_weights(depth, params.tau_plus,
                                          compensate=compensate)
    po2_ltd = params.a_minus * po2_weights(depth, params.tau_minus,
                                           compensate=compensate)
    return po2_ltp, po2_ltd


def weight_update_depth_major(w: jax.Array,
                              pre_spike: jax.Array, post_spike: jax.Array,
                              pre_bits: jax.Array, post_bits: jax.Array,
                              params: STDPParams,
                              *,
                              pairing: str = "nearest",
                              compensate: bool = True,
                              eta: float = 1.0,
                              w_min: float = 0.0,
                              w_max: float = 1.0,
                              use_kernel: bool = True,
                              interpret: bool | None = None) -> jax.Array:
    """Fused ITP-STDP update from depth-major registers, summed over a batch.

    ``pre_spike`` ``(B, n_pre)`` and ``pre_bits`` ``(depth, B, n_pre)``
    (likewise post) are a batch of samples; ``(n_pre,)`` and
    ``(depth, n_pre)`` are one.  The registers' k=0 row is most recent
    (``repro.core.history.registers_depth_major``) — the layout the kernel
    consumes with no relayout.  Returns ``clip(w + eta · Σ_b Δw_b)``;
    semantics match ``repro.core.stdp.synapse_update`` per sample
    (validated by tests/test_kernels.py and tests/test_backend.py).
    """
    n_pre, n_post = w.shape
    depth = pre_bits.shape[0]
    po2_ltp, po2_ltd = _read_vectors(params, depth, compensate)
    nearest = pairing == "nearest"
    pre_s = pre_spike.reshape(-1, n_pre).astype(jnp.float32)
    post_s = post_spike.reshape(-1, n_post).astype(jnp.float32)
    pre_b = pre_bits.reshape(depth, -1, n_pre).astype(jnp.float32)
    post_b = post_bits.reshape(depth, -1, n_post).astype(jnp.float32)
    if not use_kernel:
        return itp_stdp_update_ref(w, pre_s, post_s, pre_b, post_b,
                                   po2_ltp, po2_ltd, nearest=nearest,
                                   eta=eta, w_min=w_min, w_max=w_max)

    p_pre = _round_up(n_pre, LANE)
    p_post = _round_up(n_post, LANE)
    tb, p_b = _batch_tile(pre_s.shape[0])
    out = itp_stdp_update(
        _pad_to(_pad_to(w, p_pre, 0), p_post, 1),
        _pad_to(_pad_to(pre_s, p_b, 0), p_pre, 1),
        _pad_to(_pad_to(post_s, p_b, 0), p_post, 1),
        _pad_to(_pad_to(pre_b, p_b, 1), p_pre, 2),
        _pad_to(_pad_to(post_b, p_b, 1), p_post, 2),
        po2_ltp, po2_ltd,
        nearest=nearest, eta=eta, w_min=w_min, w_max=w_max,
        tile_pre=_tile(p_pre), tile_post=_tile(p_post), tile_batch=tb,
        interpret=_resolve_interpret(interpret),
    )
    return out[:n_pre, :n_post]


def weight_update_packed(w: jax.Array,
                         pre_spike: jax.Array, post_spike: jax.Array,
                         pre_words: jax.Array, post_words: jax.Array,
                         params: STDPParams,
                         *,
                         depth: int,
                         pairing: str = "nearest",
                         compensate: bool = True,
                         eta: float = 1.0,
                         w_min: float = 0.0,
                         w_max: float = 1.0,
                         use_kernel: bool = True,
                         interpret: bool | None = None) -> jax.Array:
    """Fused ITP-STDP update from packed uint8 history words, summed over
    a batch.

    ``pre_words`` ``(B, n_pre)`` are one ``uint8`` register word per neuron
    and sample (``repro.core.history.pack_words``, MSB = most recent) — the
    paper's 8-bit register file read in place; ``(n_pre,)`` is one sample.
    The batch and neuron axes are padded once per call, not per sample:
    zero padding is exact, as a zero word carries no history bits and a
    zero row no spikes.  Bit-identical to :func:`weight_update_depth_major`
    on the kernel path (shared fused body) and pinned against it by
    tests/test_kernels.py.
    """
    n_pre, n_post = w.shape
    po2_ltp, po2_ltd = _read_vectors(params, depth, compensate)
    nearest = pairing == "nearest"
    pre_s = pre_spike.reshape(-1, n_pre).astype(jnp.float32)
    post_s = post_spike.reshape(-1, n_post).astype(jnp.float32)
    pre_w = pre_words.reshape(-1, n_pre).astype(jnp.uint8)
    post_w = post_words.reshape(-1, n_post).astype(jnp.uint8)
    if not use_kernel:
        from repro.core.history import unpack_words
        return itp_stdp_update_ref(
            w, pre_s, post_s,
            jnp.moveaxis(unpack_words(pre_w, depth), -1, 0),
            jnp.moveaxis(unpack_words(post_w, depth), -1, 0),
            po2_ltp, po2_ltd, nearest=nearest, eta=eta,
            w_min=w_min, w_max=w_max)

    p_pre = _round_up(n_pre, LANE)
    p_post = _round_up(n_post, LANE)
    tb, p_b = _batch_tile(pre_s.shape[0])
    out = itp_stdp_update_packed(
        _pad_to(_pad_to(w, p_pre, 0), p_post, 1),
        _pad_to(_pad_to(pre_s, p_b, 0), p_pre, 1),
        _pad_to(_pad_to(post_s, p_b, 0), p_post, 1),
        _pad_to(_pad_to(pre_w, p_b, 0), p_pre, 1),
        _pad_to(_pad_to(post_w, p_b, 0), p_post, 1),
        po2_ltp, po2_ltd,
        depth=depth, nearest=nearest, eta=eta, w_min=w_min, w_max=w_max,
        tile_pre=_tile(p_pre), tile_post=_tile(p_post), tile_batch=tb,
        interpret=_resolve_interpret(interpret),
    )
    return out[:n_pre, :n_post]


def engine_weight_update(w: jax.Array,
                         pre_spike: jax.Array, post_spike: jax.Array,
                         pre_hist: SpikeHistory, post_hist: SpikeHistory,
                         params: STDPParams,
                         *,
                         pairing: str = "nearest",
                         compensate: bool = True,
                         eta: float = 1.0,
                         w_min: float = 0.0,
                         w_max: float = 1.0,
                         use_kernel: bool = True,
                         packed: bool = True,
                         interpret: bool | None = None) -> jax.Array:
    """ITP-STDP update of the full synapse matrix via the Pallas kernel.

    Drop-in accelerated replacement for ``repro.core.stdp.synapse_update``
    (same semantics, validated by tests/test_kernels.py).  ``packed=True``
    (the default) feeds the kernel one uint8 word per neuron; ``False``
    keeps the unpacked bitplane operands (the oracle datapath).  The
    routing itself is owned by ``dispatch.resolve_packed`` — this wrapper
    carries no selection logic of its own.
    """
    if resolve_packed(packed, depth=pre_hist.depth, use_kernel=use_kernel):
        return weight_update_packed(
            w, pre_spike, post_spike,
            pack_words(pre_hist), pack_words(post_hist), params,
            depth=pre_hist.depth, pairing=pairing, compensate=compensate,
            eta=eta, w_min=w_min, w_max=w_max, use_kernel=use_kernel,
            interpret=interpret)
    return weight_update_depth_major(
        w, pre_spike, post_spike,
        registers_depth_major(pre_hist), registers_depth_major(post_hist),
        params, pairing=pairing, compensate=compensate, eta=eta,
        w_min=w_min, w_max=w_max, use_kernel=use_kernel, interpret=interpret)


def synapse_delta(pre_spike: jax.Array, post_spike: jax.Array,
                  pre_bits: jax.Array, post_bits: jax.Array,
                  params: STDPParams,
                  *,
                  pairing: str = "nearest",
                  compensate: bool = True,
                  use_kernel: bool = True,
                  interpret: bool | None = None) -> jax.Array:
    """Raw Δw (pre × post) summed over a batch — no clip, no ``w``.

    ``pre_spike`` ``(B, n_pre)`` and ``pre_bits`` ``(depth, B, n_pre)``
    (likewise post; ``(n_pre,)`` / ``(depth, n_pre)`` is one sample): the
    SNN fc layers pass a whole step's batch, and the kernel contracts it
    into one Δw tile.  Callers apply eta, batch normalisation, clip and
    quantise once.  Reuses the fused kernel with one zero weight tile and
    an unbounded clip window.
    """
    n_pre = pre_bits.shape[-1]
    n_post = post_bits.shape[-1]
    zero_w = jnp.zeros((n_pre, n_post), jnp.float32)
    return weight_update_depth_major(
        zero_w, pre_spike, post_spike, pre_bits, post_bits, params,
        pairing=pairing, compensate=compensate, eta=1.0,
        w_min=float("-inf"), w_max=float("inf"),
        use_kernel=use_kernel, interpret=interpret)


def synapse_delta_packed(pre_spike: jax.Array, post_spike: jax.Array,
                         pre_words: jax.Array, post_words: jax.Array,
                         params: STDPParams,
                         *,
                         depth: int,
                         pairing: str = "nearest",
                         compensate: bool = True,
                         use_kernel: bool = True,
                         interpret: bool | None = None) -> jax.Array:
    """Raw Δw (pre × post) summed over a batch, from packed uint8 words.

    The packed twin of :func:`synapse_delta`: ``pre_words`` ``(B, n_pre)``
    / ``post_words`` ``(B, n_post)``, one byte per neuron and sample
    instead of ``4·depth`` — the SNN fc layers' fused batch path.
    """
    n_pre = pre_words.shape[-1]
    n_post = post_words.shape[-1]
    zero_w = jnp.zeros((n_pre, n_post), jnp.float32)
    return weight_update_packed(
        zero_w, pre_spike, post_spike, pre_words, post_words, params,
        depth=depth, pairing=pairing, compensate=compensate, eta=1.0,
        w_min=float("-inf"), w_max=float("inf"),
        use_kernel=use_kernel, interpret=interpret)
