"""Fused ITP-STDP synapse-update Pallas kernel.

TPU adaptation of the paper's learning-engine datapath (Figs. 9-11):

  FPGA: shift-register read → priority encode → 2's-complement → adder
  TPU : po2 read of each register plane (VPU) → pair-gated contraction of
        the batch rows (MXU) → fused w += Δw, clip

The update of one step over a batch of B samples is

    dw[k, c] = Σ_b (1 − pre[b, k]) · ltp[b, k] · post[b, c]
             − Σ_b pre[b, k] · (1 − post[b, c]) · ltd[b, c]

(the XOR/AND gate of §V-A on {0,1} spikes: potentiate where post fired
alone, depress where pre fired alone), with ``ltp`` / ``ltd`` the po2
reads of the pre / post registers.  B = 1 is the engine's rank-1 update.

Layout choices (HW-codesign reasoning):
  * spikes and history words arrive as ``(B, N)`` rows, bitplanes as
    ``(depth, B, N)``: the neuron axis sits on the 128-wide lanes, the
    batch rows on the sublanes;
  * the grid is (pre tiles, post tiles, batch tiles) with the batch axis
    innermost: it is the reduction axis, and the ``(TP, TQ)`` output tile
    stays resident in VMEM across it — each batch tile accumulates its two
    batch-contracting MXU dots into it, and the last one applies the
    clipped read-modify-write, so the weights make one HBM round-trip per
    step whatever B is;
  * the po2 read runs one register plane at a time (shift+mask of the
    word, the MSB mask as a running "seen" plane, one SMEM place value per
    depth slot), so the ``(depth, TB, T)`` bitplanes never exist at once.

The kernel covers both pairing modes of §II-B with one code path: the
nearest-neighbour MSB mask (Fig. 11) keeps the first '1' of each register,
the all-to-all fixed-point read (Fig. 3) uses the raw bits; both then sum
the planes against the po2 vector, which carries the place values
2^(-k/τ') (place value 2^-k exactly in the hardware regime τ' = 1).

Every op in the bodies must lower through Mosaic, which has no ``cumsum``
and no row→column gather: the MSB mask is an unrolled scan and both
contractions take the batch rows as their contracting dimension.  The
dots ask for full f32 contract precision; at Mosaic's default a single
bf16 MXU pass would round each magnitude (2^-9 relative).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _po2_read(plane, depth: int, po2_ref, *, nearest: bool) -> jax.Array:
    """Σ_k po2[k] · bit_k over the register, k ascending, one plane at a time.

    ``plane(k)`` gives depth slot k (k = 0 most recent) as f32 ({0,1} bits;
    a rank-1 rule passes its magnitudes as one slot); ``po2_ref`` is the
    ``(1, depth)`` place-value row in SMEM.  With ``nearest`` each plane
    is first masked by the running "seen" plane: Fig. 11's MSB priority
    encode, which keeps only the first '1' scanning most-recent-first
    (``bits * (cumsum(bits) == 1)`` on {0,1}).
    """
    acc = seen = None
    for k in range(depth):
        bit = plane(k)
        if nearest:
            if seen is None:
                seen = bit
            else:
                bit = bit * (1.0 - seen)
                seen = seen + bit
        term = po2_ref[0, k] * bit
        acc = term if acc is None else acc + term
    return acc


def _stdp_body(pre_plane, post_plane, pre_ref, post_ref, po2_ltp_ref,
               po2_ltd_ref, w_ref, out_ref, *, depth: int, nearest: bool,
               eta: float, w_min: float, w_max: float):
    """Shared fused datapath: po2 read → pair gate → batch contraction → RMW.

    Both kernel variants (bitplane-fed and packed-word-fed) route through
    this body, so the packed path is bit-identical to the unpacked one by
    construction.
    """
    ltp_mag = _po2_read(pre_plane, depth, po2_ltp_ref, nearest=nearest)   # (TB, TP)
    ltd_mag = _po2_read(post_plane, depth, po2_ltd_ref, nearest=nearest)  # (TB, TQ)

    # pair gate (§V-A): potentiate where post fired alone, depress where pre
    # fired alone; contract the batch rows on the MXU
    pre = pre_ref[...]      # (TB, TP) {0,1}
    post = post_ref[...]    # (TB, TQ) {0,1}
    contract = (((0,), (0,)), ((), ()))
    hi = jax.lax.Precision.HIGHEST
    dw_ltp = jax.lax.dot_general((1.0 - pre) * ltp_mag, post, contract,
                                 precision=hi,
                                 preferred_element_type=jnp.float32)
    dw_ltd = jax.lax.dot_general(pre, (1.0 - post) * ltd_mag, contract,
                                 precision=hi,
                                 preferred_element_type=jnp.float32)

    b = pl.program_id(2)

    @pl.when(b == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += dw_ltp - dw_ltd

    @pl.when(b == pl.num_programs(2) - 1)
    def _apply():
        out_ref[...] = jnp.clip(w_ref[...] + eta * out_ref[...], w_min, w_max)


def _stdp_kernel(pre_spike_ref, post_spike_ref, pre_hist_ref, post_hist_ref,
                 po2_ltp_ref, po2_ltd_ref, w_ref, out_ref, *, depth: int,
                 nearest: bool, eta: float, w_min: float, w_max: float):
    # (depth, TB, TP) / (depth, TB, TQ) bitplanes, {0,1}
    _stdp_body(lambda k: pre_hist_ref[k], lambda k: post_hist_ref[k],
               pre_spike_ref, post_spike_ref, po2_ltp_ref, po2_ltd_ref,
               w_ref, out_ref, depth=depth, nearest=nearest, eta=eta,
               w_min=w_min, w_max=w_max)


def _stdp_packed_kernel(pre_spike_ref, post_spike_ref, pre_word_ref,
                        post_word_ref, po2_ltp_ref, po2_ltd_ref, w_ref,
                        out_ref, *, depth: int, nearest: bool, eta: float,
                        w_min: float, w_max: float):
    # (TB, TP) / (TB, TQ) packed uint8 history words — one byte per neuron
    # crosses HBM; each bitplane is a shift+mask in-register (paper eq. 2 /
    # Fig. 3: bit k of the register sits at word bit 7 − k, MSB = most
    # recent, ``repro.core.history.pack_words``)
    pre_w = pre_word_ref[...].astype(jnp.int32)
    post_w = post_word_ref[...].astype(jnp.int32)

    def plane(words):
        return lambda k: ((words >> (7 - k)) & 1).astype(jnp.float32)

    _stdp_body(plane(pre_w), plane(post_w), pre_spike_ref, post_spike_ref,
               po2_ltp_ref, po2_ltd_ref, w_ref, out_ref, depth=depth,
               nearest=nearest, eta=eta, w_min=w_min, w_max=w_max)


def _batched_call(kern, name, w, pre_spike, post_spike, pre_hist, post_hist,
                  po2_ltp, po2_ltd, *, depth, tile_pre, tile_post, tile_batch,
                  interpret):
    """The ``pallas_call`` both variants share: ``(B, N)`` spike rows,
    history operands with the batch on their second-to-last axis."""
    n_pre, n_post = w.shape
    batch = pre_spike.shape[0]
    tp = min(tile_pre, n_pre)
    tq = min(tile_post, n_post)
    tb = min(tile_batch, batch)
    if n_pre % tp or n_post % tq:
        raise ValueError(f"tile sizes ({tp},{tq}) must divide ({n_pre},{n_post})")
    if batch % tb:
        raise ValueError(f"tile_batch={tb} must divide the batch {batch}")

    hist = pre_hist.shape[:-2]      # () for words, (depth,) for bitplanes
    lead = (0,) * len(hist)
    po2 = pl.BlockSpec((1, depth), lambda i, j, b: (0, 0),
                       memory_space=pltpu.SMEM)
    return pl.pallas_call(
        kern,
        name=name,
        grid=(n_pre // tp, n_post // tq, batch // tb),
        in_specs=[
            pl.BlockSpec((tb, tp), lambda i, j, b: (b, i)),        # pre spikes
            pl.BlockSpec((tb, tq), lambda i, j, b: (b, j)),        # post spikes
            pl.BlockSpec(hist + (tb, tp), lambda i, j, b: lead + (b, i)),
            pl.BlockSpec(hist + (tb, tq), lambda i, j, b: lead + (b, j)),
            po2,                                                   # po2 LTP
            po2,                                                   # po2 LTD
            pl.BlockSpec((tp, tq), lambda i, j, b: (i, j)),        # w
        ],
        out_specs=pl.BlockSpec((tp, tq), lambda i, j, b: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n_pre, n_post), jnp.float32),
        interpret=interpret,
    )(
        pre_spike.astype(jnp.float32),
        post_spike.astype(jnp.float32),
        pre_hist,
        post_hist,
        po2_ltp.reshape(1, depth).astype(jnp.float32),
        po2_ltd.reshape(1, depth).astype(jnp.float32),
        w.astype(jnp.float32),
    )


@functools.partial(
    jax.jit,
    static_argnames=("nearest", "eta", "w_min", "w_max", "tile_pre",
                     "tile_post", "tile_batch", "interpret"),
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
                    tile_batch: int = 128,
                    interpret: bool = False) -> jax.Array:
    """Fused ITP-STDP weight update over a batch of samples.

    Args:
      w:          (n_pre, n_post) float32 synapse matrix.
      pre_spike:  (B, n_pre)  current-step spikes {0,1}; (n_pre,) is B = 1.
      post_spike: (B, n_post) current-step spikes {0,1}.
      pre_hist:   (depth, B, n_pre)  bitplanes, k=0 row = most recent;
                  (depth, n_pre) is B = 1.
      post_hist:  (depth, B, n_post) bitplanes.
      po2_ltp:    (depth,) LTP read vector  A+·2^(-k/τ').
      po2_ltd:    (depth,) LTD read vector  A-·2^(-k/τ').
      nearest:    nearest-neighbour (True) or all-to-all (False) pairing.
      tile_batch: batch rows per grid step; must divide B (or exceed it).
      interpret:  run the kernel body in interpret mode (CPU validation);
                  the default False targets real accelerator hardware.

    Returns ``clip(w + eta · Σ_b Δw_b, w_min, w_max)``.
    """
    n_pre, n_post = w.shape
    depth = pre_hist.shape[0]
    kern = functools.partial(_stdp_kernel, depth=depth, nearest=nearest,
                             eta=eta, w_min=w_min, w_max=w_max)
    return _batched_call(
        kern, "itp_stdp_update", w,
        pre_spike.reshape(-1, n_pre), post_spike.reshape(-1, n_post),
        pre_hist.reshape(depth, -1, n_pre).astype(jnp.float32),
        post_hist.reshape(depth, -1, n_post).astype(jnp.float32),
        po2_ltp, po2_ltd, depth=depth, tile_pre=tile_pre,
        tile_post=tile_post, tile_batch=tile_batch, interpret=interpret)


@functools.partial(
    jax.jit,
    static_argnames=("depth", "nearest", "eta", "w_min", "w_max", "tile_pre",
                     "tile_post", "tile_batch", "interpret"),
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
                           tile_batch: int = 128,
                           interpret: bool = False) -> jax.Array:
    """Fused ITP-STDP batch update fed by packed uint8 history words.

    The storage-format variant of :func:`itp_stdp_update`: instead of
    ``(depth, B, N)`` float32 bitplanes (``4·depth`` bytes of HBM traffic
    per neuron and sample) the kernel reads **one uint8 word per neuron
    and sample** — the hardware register file of the paper (Figs. 3/11) —
    and unpacks each bitplane in-register (shift+mask per depth slot)
    before the identical po2 read, pair gate and batch contraction.
    Bit-identical to the unpacked kernel by construction (shared
    ``_stdp_body``).

    Args:
      w:          (n_pre, n_post) float32 synapse matrix.
      pre_spike:  (B, n_pre)  current-step spikes {0,1}; (n_pre,) is B = 1.
      post_spike: (B, n_post) current-step spikes {0,1}.
      pre_words:  (B, n_pre)  uint8 packed registers, MSB = most recent
                  (``repro.core.history.pack_words``).
      post_words: (B, n_post) uint8 packed registers.
      po2_ltp:    (depth,) LTP read vector  A+·2^(-k/τ').
      po2_ltd:    (depth,) LTD read vector  A-·2^(-k/τ').
      depth:      logical register depth (≤ 8).
      nearest:    nearest-neighbour (True) or all-to-all (False) pairing.
      tile_batch: batch rows per grid step; must divide B (or exceed it).
      interpret:  run the kernel body in interpret mode (CPU validation);
                  the default False targets real accelerator hardware.

    Returns ``clip(w + eta · Σ_b Δw_b, w_min, w_max)``.
    """
    if depth > 8:
        raise ValueError("packed history words support depth <= 8")
    n_pre, n_post = w.shape
    kern = functools.partial(_stdp_packed_kernel, depth=depth,
                             nearest=nearest, eta=eta, w_min=w_min,
                             w_max=w_max)
    return _batched_call(
        kern, "itp_stdp_update_packed", w,
        pre_spike.reshape(-1, n_pre), post_spike.reshape(-1, n_post),
        pre_words.reshape(-1, n_pre).astype(jnp.uint8),
        post_words.reshape(-1, n_post).astype(jnp.uint8),
        po2_ltp, po2_ltd, depth=depth, tile_pre=tile_pre,
        tile_post=tile_post, tile_batch=tile_batch, interpret=interpret)
