"""Fused im2col ITP-STDP conv-update Pallas kernel.

The conv layers apply the pair-based STDP rule per (patch element ->
output channel) synapse, accumulated over batch and spatial positions
(src/repro/models/snn.py).  After im2col the whole update collapses to

    dw[k, c] = sum_m (1 - pre[m, k]) * ltp_mag[m, k] * post[m, c]
             - sum_m pre[m, k] * (1 - post[m, c]) * ltd_mag[m, c]

where m runs over the M = batch x positions patch rows and the LTP/LTD
magnitudes are the po2 reads of the spike-history bitplanes — two MXU
matmuls contracting the large M axis, fused with the history read and the
pair gating in one pass.

Layout choices (HW-codesign reasoning, mirroring the dense itp_stdp
kernel):
  * the patch rows M sit on the grid + sublane axis; the small patch
    width K and channel count C are padded to the 128-lane boundary by
    ops.py, so both matmuls are MXU-aligned;
  * bitplanes arrive depth-major (depth, TM, K): the po2 read is a
    length-depth multiply-accumulate over the leading axis, one SMEM
    scalar place value per depth slot, kept entirely in VREGs;
  * the (K, C) delta tile stays resident in VMEM across the whole grid —
    each grid step accumulates its tile's two dot products into it, so the
    weight delta makes exactly one HBM round-trip;
  * both matmuls ask for full f32 contract precision: the magnitude
    operand carries arbitrary f32 place values that a single bf16 MXU
    pass would round.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


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


def _unpack_bits(words: jax.Array, depth: int) -> jax.Array:
    """In-register bitplane unpack: (TM, X) uint8 words → (depth, TM, X) f32.

    Shift+mask per depth slot (paper eq. 2 / Fig. 3): bit k of the logical
    register sits at word bit ``7 - k`` (MSB = most recent,
    ``repro.core.history.pack_words``).  The bitplanes never touch HBM —
    only the one byte per patch element does.
    """
    w = words.astype(jnp.int32)
    planes = [((w >> (7 - k)) & 1)[None] for k in range(depth)]
    return jnp.concatenate(planes, axis=0).astype(jnp.float32)


def _po2_read(po2_ref, bits: jax.Array) -> jax.Array:
    """Σ_k po2[k] · bits[k] over the leading depth axis, k ascending.

    ``po2_ref`` is the ``(1, depth)`` place-value row in SMEM: each depth
    slot reads one scalar and scales its whole plane.
    """
    acc = po2_ref[0, 0] * bits[0]
    for k in range(1, bits.shape[0]):
        acc = acc + po2_ref[0, k] * bits[k]
    return acc


def _conv_stdp_body(
    pre, post, pre_bits, post_bits, po2_ltp_ref, po2_ltd_ref, out_ref, *, nearest: bool
):
    """Shared fused conv datapath: po2 read → pair gate → two MXU matmuls.

    Both kernel variants (bitplane-fed and packed-word-fed) route through
    this body, so the packed path is bit-identical to the unpacked one by
    construction.
    """
    if nearest:
        pre_bits = first_one_mask(pre_bits)
        post_bits = first_one_mask(post_bits)

    # po2 read: reduce the depth axis against the place-value vector — the
    # 'register read IS the weight update' step, per patch element
    ltp_mag = _po2_read(po2_ltp_ref, pre_bits)  # (TM, K)
    ltd_mag = _po2_read(po2_ltd_ref, post_bits)  # (TM, C)

    # XOR/AND pair gate: potentiate where post fired alone, depress where
    # pre fired alone; contract the patch-row axis on the MXU
    contract = (((0,), (0,)), ((), ()))
    ltp_term = (1.0 - pre) * ltp_mag  # (TM, K)
    ltd_term = (1.0 - post) * ltd_mag  # (TM, C)
    hi = jax.lax.Precision.HIGHEST
    dw_ltp = jax.lax.dot_general(
        ltp_term, post, contract, precision=hi, preferred_element_type=jnp.float32
    )
    dw_ltd = jax.lax.dot_general(
        pre, ltd_term, contract, precision=hi, preferred_element_type=jnp.float32
    )

    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += dw_ltp - dw_ltd


def _conv_stdp_kernel(
    pre_ref,
    post_ref,
    pre_bits_ref,
    post_bits_ref,
    po2_ltp_ref,
    po2_ltd_ref,
    out_ref,
    *,
    nearest: bool,
):
    pre = pre_ref[...].astype(jnp.float32)  # (TM, K)
    post = post_ref[...].astype(jnp.float32)  # (TM, C)
    pre_bits = pre_bits_ref[...].astype(jnp.float32)  # (depth, TM, K)
    post_bits = post_bits_ref[...].astype(jnp.float32)  # (depth, TM, C)
    _conv_stdp_body(
        pre, post, pre_bits, post_bits, po2_ltp_ref, po2_ltd_ref, out_ref, nearest=nearest
    )


def _conv_stdp_packed_kernel(
    pre_ref,
    post_ref,
    pre_word_ref,
    post_word_ref,
    po2_ltp_ref,
    po2_ltd_ref,
    out_ref,
    *,
    depth: int,
    nearest: bool,
):
    pre = pre_ref[...].astype(jnp.float32)  # (TM, K)
    post = post_ref[...].astype(jnp.float32)  # (TM, C)
    # (TM, K) / (TM, C) packed uint8 words — one byte per patch element
    # crosses HBM; the (depth, TM, ·) bitplanes exist only in-register
    pre_bits = _unpack_bits(pre_word_ref[...], depth)
    post_bits = _unpack_bits(post_word_ref[...], depth)
    _conv_stdp_body(
        pre, post, pre_bits, post_bits, po2_ltp_ref, po2_ltd_ref, out_ref, nearest=nearest
    )


@functools.partial(
    jax.jit,
    static_argnames=("nearest", "tile_m", "interpret"),
)
def itp_stdp_conv_delta(
    pre_patches: jax.Array,
    post_spikes: jax.Array,
    pre_bits: jax.Array,
    post_bits: jax.Array,
    po2_ltp: jax.Array,
    po2_ltd: jax.Array,
    *,
    nearest: bool = True,
    tile_m: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Patch-level fused ITP-STDP conv weight delta.

    Args:
      pre_patches: (M, K) im2col spike patches, M = batch x output positions.
      post_spikes: (M, C) current-step output spikes.
      pre_bits:    (depth, M, K) bitplane patches, k=0 row most recent.
      post_bits:   (depth, M, C) output bitplanes.
      po2_ltp:     (depth,) LTP read vector (A+ amplitude folded in).
      po2_ltd:     (depth,) LTD read vector (A- amplitude folded in).
      nearest:     nearest-neighbour (True) or all-to-all (False) pairing.
      tile_m:      patch rows per grid step; must divide M.
      interpret:   run through the Pallas interpreter (CPU validation);
                   the default False targets real accelerator hardware.

    Returns the (K, C) float32 delta accumulated over all M patch rows.
    """
    m, kk = pre_patches.shape
    cc = post_spikes.shape[1]
    depth = pre_bits.shape[0]
    tm = min(tile_m, m)
    if m % tm:
        raise ValueError(f"tile_m={tm} must divide M={m}")

    kern = functools.partial(_conv_stdp_kernel, nearest=nearest)
    return pl.pallas_call(
        kern,
        name="itp_stdp_conv_delta",
        grid=(m // tm,),
        in_specs=[
            pl.BlockSpec((tm, kk), lambda i: (i, 0)),  # pre patches
            pl.BlockSpec((tm, cc), lambda i: (i, 0)),  # post spikes
            pl.BlockSpec((depth, tm, kk), lambda i: (0, i, 0)),  # pre bitplanes
            pl.BlockSpec((depth, tm, cc), lambda i: (0, i, 0)),  # post bitplanes
            pl.BlockSpec((1, depth), lambda i: (0, 0), memory_space=pltpu.SMEM),  # po2 LTP
            pl.BlockSpec((1, depth), lambda i: (0, 0), memory_space=pltpu.SMEM),  # po2 LTD
        ],
        out_specs=pl.BlockSpec((kk, cc), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((kk, cc), jnp.float32),
        interpret=interpret,
    )(
        pre_patches.astype(jnp.float32),
        post_spikes.astype(jnp.float32),
        pre_bits.astype(jnp.float32),
        post_bits.astype(jnp.float32),
        po2_ltp.reshape(1, depth).astype(jnp.float32),
        po2_ltd.reshape(1, depth).astype(jnp.float32),
    )


@functools.partial(
    jax.jit,
    static_argnames=("depth", "nearest", "tile_m", "interpret"),
)
def itp_stdp_conv_delta_packed(
    pre_patches: jax.Array,
    post_spikes: jax.Array,
    pre_words: jax.Array,
    post_words: jax.Array,
    po2_ltp: jax.Array,
    po2_ltd: jax.Array,
    *,
    depth: int,
    nearest: bool = True,
    tile_m: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Patch-level fused conv delta fed by packed uint8 history words.

    The storage-format variant of :func:`itp_stdp_conv_delta`: the history
    operands are one uint8 register word per patch element / output neuron
    (``repro.core.history.pack_words``, MSB = most recent) instead of
    ``(depth, M, ·)`` float32 bitplanes — a ``4·depth``× reduction of the
    dominant HBM stream.  Bitplanes are unpacked in-register (shift+mask
    per depth slot) before the identical po2 read, pair gate, and patch-row
    matmuls (shared ``_conv_stdp_body`` → bit-identical by construction).

    Args:
      pre_patches: (M, K) im2col spike patches, M = batch x output positions.
      post_spikes: (M, C) current-step output spikes.
      pre_words:   (M, K) uint8 packed history words in the same im2col
                   patch layout as ``pre_patches``.
      post_words:  (M, C) uint8 packed output-history words.
      po2_ltp:     (depth,) LTP read vector (A+ amplitude folded in).
      po2_ltd:     (depth,) LTD read vector (A- amplitude folded in).
      depth:       logical register depth (≤ 8).
      nearest:     nearest-neighbour (True) or all-to-all (False) pairing.
      tile_m:      patch rows per grid step; must divide M.
      interpret:   run through the Pallas interpreter (CPU validation);
                   the default False targets real accelerator hardware.

    Returns the (K, C) float32 delta accumulated over all M patch rows.
    """
    if depth > 8:
        raise ValueError("packed history words support depth <= 8")
    m, kk = pre_patches.shape
    cc = post_spikes.shape[1]
    tm = min(tile_m, m)
    if m % tm:
        raise ValueError(f"tile_m={tm} must divide M={m}")

    kern = functools.partial(_conv_stdp_packed_kernel, depth=depth, nearest=nearest)
    return pl.pallas_call(
        kern,
        name="itp_stdp_conv_delta_packed",
        grid=(m // tm,),
        in_specs=[
            pl.BlockSpec((tm, kk), lambda i: (i, 0)),  # pre patches
            pl.BlockSpec((tm, cc), lambda i: (i, 0)),  # post spikes
            pl.BlockSpec((tm, kk), lambda i: (i, 0)),  # pre packed words
            pl.BlockSpec((tm, cc), lambda i: (i, 0)),  # post packed words
            pl.BlockSpec((1, depth), lambda i: (0, 0), memory_space=pltpu.SMEM),  # po2 LTP
            pl.BlockSpec((1, depth), lambda i: (0, 0), memory_space=pltpu.SMEM),  # po2 LTD
        ],
        out_specs=pl.BlockSpec((kk, cc), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((kk, cc), jnp.float32),
        interpret=interpret,
    )(
        pre_patches.astype(jnp.float32),
        post_spikes.astype(jnp.float32),
        pre_words.astype(jnp.uint8),
        post_words.astype(jnp.uint8),
        po2_ltp.reshape(1, depth).astype(jnp.float32),
        po2_ltd.reshape(1, depth).astype(jnp.float32),
    )
