"""Fused ITP-STDP synapse-update Pallas kernel.

TPU adaptation of the paper's learning-engine datapath (Figs. 9-11):

  FPGA: shift-register read → priority encode → 2's-complement → adder
  TPU : bitplane dot with the po2 place-value vector (VPU/MXU) → outer
        LTP/LTD gating (the XOR/AND control logic) → fused w += Δw, clip

Layout choices (HW-codesign reasoning):
  * spike histories are stored **depth-major** ``(depth, N)`` so the neuron
    axis sits on the 128-wide lane dimension and the (≤8)-deep history on
    the sublane dimension — the po2 read is an 8-element reduction per lane,
    which the Mosaic compiler keeps entirely in VREGs;
  * the weight tile ``(TP, TQ)`` lives in VMEM for the whole fused
    read-modify-write — one HBM round-trip per tile instead of the three
    (read Δw operands, read w, write w) a composed implementation costs;
  * LTP/LTD magnitudes are rank-1 per tile row/col, so Δw is an outer
    product accumulate — MXU-aligned when TP, TQ are multiples of 8/128.

The kernel covers both pairing modes of §II-B with one code path: the
nearest-neighbour MSB mask (Fig. 11) keeps the first '1' of each register
(:func:`first_one_mask`), the all-to-all fixed-point read (Fig. 3) uses
the raw bits; both then dot with the po2 vector, which carries the place
values 2^(-k/τ') (place value 2^-k exactly in the hardware regime τ' = 1).

Every op in the bodies must lower through Mosaic, which has no ``cumsum``
and no row→column gather: the MSB mask is an unrolled scan and pre-side
columns are transposes of the ``(1, T)`` rows.  The po2 dot asks for full
f32 contract precision; at Mosaic's default a single bf16 MXU pass would
round each place value (2^-9 relative).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _unpack_bits(words: jax.Array, depth: int) -> jax.Array:
    """In-register bitplane unpack: (1, T) uint8 words → (depth, T) f32.

    The shift+mask per depth slot of the paper's 8-bit register read (eq. 2
    / Fig. 3): bit k of the logical register sits at word bit ``7 - k``
    (MSB = most recent, ``repro.core.history.pack_words``).  Stays entirely
    in VREGs — the only HBM traffic is the one byte per neuron.
    """
    w = words.astype(jnp.int32)
    planes = [(w >> (7 - k)) & 1 for k in range(depth)]
    return jnp.concatenate(planes, axis=0).astype(jnp.float32)


def first_one_mask(bits: jax.Array) -> jax.Array:
    """Fig. 11 MSB priority encode on {0,1} planes stacked along axis 0.

    Keeps only the first '1' scanning most-recent-first — exactly
    ``bits * (cumsum(bits, axis=0) == 1)`` on {0,1} — as an unrolled scan
    over the static depth (≤ 8) with a running "seen" plane.
    """
    seen = jnp.zeros_like(bits[0:1])
    rows = []
    for k in range(bits.shape[0]):
        row = bits[k:k + 1] * (1.0 - seen)
        rows.append(row)
        seen = seen + row
    return jnp.concatenate(rows, axis=0)


def _stdp_body(pre_bits, post_bits, pre_spike_ref, post_spike_ref,
               po2_ltp_ref, po2_ltd_ref, w_ref, out_ref, *,
               nearest: bool, eta: float, w_min: float, w_max: float):
    """Shared fused datapath: po2 read → XOR pair gate → clipped RMW.

    Both kernel variants (bitplane-fed and packed-word-fed) route through
    this body, so the packed path is bit-identical to the unpacked one by
    construction.
    """
    if nearest:
        pre_bits = first_one_mask(pre_bits)
        post_bits = first_one_mask(post_bits)

    # po2 read: (1, depth) @ (depth, T) -> (1, T); the 'register read IS the
    # weight update' step.  po2 vectors include the A± amplitudes.
    hi = jax.lax.Precision.HIGHEST
    ltp_mag = jnp.dot(po2_ltp_ref[...], pre_bits, precision=hi)    # (1, TP)
    ltd_mag = jnp.dot(po2_ltd_ref[...], post_bits, precision=hi)   # (1, TQ)

    # XOR/AND control logic (§V-A): update only when exactly one side fired
    pre_s = pre_spike_ref[...].astype(jnp.float32)     # (1, TP)
    post_s = post_spike_ref[...].astype(jnp.float32)   # (1, TQ)
    pre_col = jnp.transpose(pre_s)                     # (TP, 1)
    fire_xor = pre_col + post_s - 2.0 * pre_col * post_s   # XOR on {0,1}
    ltp_en = fire_xor * post_s                   # post fired alone
    ltd_en = fire_xor * pre_col                  # pre fired alone

    dw = ltp_en * jnp.transpose(ltp_mag) - ltd_en * ltd_mag
    out_ref[...] = jnp.clip(w_ref[...] + eta * dw, w_min, w_max)


def _stdp_kernel(pre_spike_ref, post_spike_ref, pre_hist_ref, post_hist_ref,
                 po2_ltp_ref, po2_ltd_ref, w_ref, out_ref, *,
                 nearest: bool, eta: float, w_min: float, w_max: float):
    # (depth, TP) / (depth, TQ) bitplanes, {0,1}
    pre_bits = pre_hist_ref[...].astype(jnp.float32)
    post_bits = post_hist_ref[...].astype(jnp.float32)
    _stdp_body(pre_bits, post_bits, pre_spike_ref, post_spike_ref,
               po2_ltp_ref, po2_ltd_ref, w_ref, out_ref,
               nearest=nearest, eta=eta, w_min=w_min, w_max=w_max)


def _stdp_packed_kernel(pre_spike_ref, post_spike_ref, pre_word_ref,
                        post_word_ref, po2_ltp_ref, po2_ltd_ref, w_ref,
                        out_ref, *, depth: int, nearest: bool, eta: float,
                        w_min: float, w_max: float):
    # (1, TP) / (1, TQ) packed uint8 history words — one byte per neuron
    # crosses HBM; the bitplanes exist only in-register
    pre_bits = _unpack_bits(pre_word_ref[...], depth)     # (depth, TP)
    post_bits = _unpack_bits(post_word_ref[...], depth)   # (depth, TQ)
    _stdp_body(pre_bits, post_bits, pre_spike_ref, post_spike_ref,
               po2_ltp_ref, po2_ltd_ref, w_ref, out_ref,
               nearest=nearest, eta=eta, w_min=w_min, w_max=w_max)


@functools.partial(
    jax.jit,
    static_argnames=("nearest", "eta", "w_min", "w_max", "tile_pre",
                     "tile_post", "interpret"),
)
def itp_stdp_update(w: jax.Array,
                    pre_spike: jax.Array, post_spike: jax.Array,
                    pre_hist: jax.Array, post_hist: jax.Array,
                    po2_ltp: jax.Array, po2_ltd: jax.Array,
                    *,
                    nearest: bool = True,
                    eta: float = 1.0,
                    w_min: float = 0.0,
                    w_max: float = 1.0,
                    tile_pre: int = 256,
                    tile_post: int = 256,
                    interpret: bool = False) -> jax.Array:
    """Fused ITP-STDP weight update.

    Args:
      w:          (n_pre, n_post) float32 synapse matrix.
      pre_spike:  (n_pre,)  current-step spikes {0,1}.
      post_spike: (n_post,) current-step spikes {0,1}.
      pre_hist:   (depth, n_pre)  bitplanes, k=0 row = most recent.
      post_hist:  (depth, n_post) bitplanes.
      po2_ltp:    (depth,) LTP read vector  A+·2^(-k/τ').
      po2_ltd:    (depth,) LTD read vector  A-·2^(-k/τ').
      nearest:    nearest-neighbour (True) or all-to-all (False) pairing.
      interpret:  run the kernel body in interpret mode (CPU validation);
                  the default False targets real accelerator hardware.

    Returns the updated, clipped weight matrix.
    """
    n_pre, n_post = w.shape
    depth = pre_hist.shape[0]
    tp = min(tile_pre, n_pre)
    tq = min(tile_post, n_post)
    if n_pre % tp or n_post % tq:
        raise ValueError(f"tile sizes ({tp},{tq}) must divide ({n_pre},{n_post})")

    grid = (n_pre // tp, n_post // tq)
    kern = functools.partial(_stdp_kernel, nearest=nearest, eta=eta,
                             w_min=w_min, w_max=w_max)
    return pl.pallas_call(
        kern,
        name="itp_stdp_update",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tp), lambda i, j: (0, i)),        # pre_spike
            pl.BlockSpec((1, tq), lambda i, j: (0, j)),        # post_spike
            pl.BlockSpec((depth, tp), lambda i, j: (0, i)),    # pre_hist
            pl.BlockSpec((depth, tq), lambda i, j: (0, j)),    # post_hist
            pl.BlockSpec((1, depth), lambda i, j: (0, 0)),     # po2_ltp
            pl.BlockSpec((1, depth), lambda i, j: (0, 0)),     # po2_ltd
            pl.BlockSpec((tp, tq), lambda i, j: (i, j)),       # w
        ],
        out_specs=pl.BlockSpec((tp, tq), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n_pre, n_post), jnp.float32),
        interpret=interpret,
    )(
        pre_spike.reshape(1, n_pre).astype(jnp.float32),
        post_spike.reshape(1, n_post).astype(jnp.float32),
        pre_hist.astype(jnp.float32),
        post_hist.astype(jnp.float32),
        po2_ltp.reshape(1, depth).astype(jnp.float32),
        po2_ltd.reshape(1, depth).astype(jnp.float32),
        w.astype(jnp.float32),
    )


@functools.partial(
    jax.jit,
    static_argnames=("depth", "nearest", "eta", "w_min", "w_max", "tile_pre",
                     "tile_post", "interpret"),
)
def itp_stdp_update_packed(w: jax.Array,
                           pre_spike: jax.Array, post_spike: jax.Array,
                           pre_words: jax.Array, post_words: jax.Array,
                           po2_ltp: jax.Array, po2_ltd: jax.Array,
                           *,
                           depth: int,
                           nearest: bool = True,
                           eta: float = 1.0,
                           w_min: float = 0.0,
                           w_max: float = 1.0,
                           tile_pre: int = 256,
                           tile_post: int = 256,
                           interpret: bool = False) -> jax.Array:
    """Fused ITP-STDP update fed by packed uint8 history words.

    The storage-format variant of :func:`itp_stdp_update`: instead of
    ``(depth, N)`` float32 bitplanes (``4·depth`` bytes of HBM traffic per
    neuron) the kernel reads **one uint8 word per neuron** — the hardware
    register file of the paper (Figs. 3/11) — and unpacks the bitplanes
    in-register (shift+mask per depth slot) before the identical po2 dot
    and XOR pair-gate.  Bit-identical to the unpacked kernel by
    construction (shared ``_stdp_body``).

    Args:
      w:          (n_pre, n_post) float32 synapse matrix.
      pre_spike:  (n_pre,)  current-step spikes {0,1}.
      post_spike: (n_post,) current-step spikes {0,1}.
      pre_words:  (n_pre,)  uint8 packed registers, MSB = most recent
                  (``repro.core.history.pack_words``).
      post_words: (n_post,) uint8 packed registers.
      po2_ltp:    (depth,) LTP read vector  A+·2^(-k/τ').
      po2_ltd:    (depth,) LTD read vector  A-·2^(-k/τ').
      depth:      logical register depth (≤ 8).
      nearest:    nearest-neighbour (True) or all-to-all (False) pairing.
      interpret:  run the kernel body in interpret mode (CPU validation);
                  the default False targets real accelerator hardware.

    Returns the updated, clipped weight matrix.
    """
    if depth > 8:
        raise ValueError("packed history words support depth <= 8")
    n_pre, n_post = w.shape
    tp = min(tile_pre, n_pre)
    tq = min(tile_post, n_post)
    if n_pre % tp or n_post % tq:
        raise ValueError(f"tile sizes ({tp},{tq}) must divide ({n_pre},{n_post})")

    grid = (n_pre // tp, n_post // tq)
    kern = functools.partial(_stdp_packed_kernel, depth=depth,
                             nearest=nearest, eta=eta, w_min=w_min,
                             w_max=w_max)
    return pl.pallas_call(
        kern,
        name="itp_stdp_update_packed",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tp), lambda i, j: (0, i)),        # pre_spike
            pl.BlockSpec((1, tq), lambda i, j: (0, j)),        # post_spike
            pl.BlockSpec((1, tp), lambda i, j: (0, i)),        # pre_words
            pl.BlockSpec((1, tq), lambda i, j: (0, j)),        # post_words
            pl.BlockSpec((1, depth), lambda i, j: (0, 0)),     # po2_ltp
            pl.BlockSpec((1, depth), lambda i, j: (0, 0)),     # po2_ltd
            pl.BlockSpec((tp, tq), lambda i, j: (i, j)),       # w
        ],
        out_specs=pl.BlockSpec((tp, tq), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n_pre, n_post), jnp.float32),
        interpret=interpret,
    )(
        pre_spike.reshape(1, n_pre).astype(jnp.float32),
        post_spike.reshape(1, n_post).astype(jnp.float32),
        pre_words.reshape(1, n_pre).astype(jnp.uint8),
        post_words.reshape(1, n_post).astype(jnp.uint8),
        po2_ltp.reshape(1, depth).astype(jnp.float32),
        po2_ltd.reshape(1, depth).astype(jnp.float32),
        w.astype(jnp.float32),
    )
