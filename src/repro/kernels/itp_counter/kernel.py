"""Fused counter-rule (explicit-Δt STDP) Pallas kernels.

The conventional learning datapath the paper measures ITP-STDP against
(Tables III-V), implemented the way prior explicit-Δt accelerators do it
on-chip: the per-neuron last-spike counter word is read once from HBM,
the per-pair timing difference is formed **in-register** by broadcasting
the counter across the synapse tile, and the rule's window function is
evaluated per pair, fused with the XOR pair gate and the clipped weight
read-modify-write — one HBM round-trip per weight tile, exactly like the
``itp_stdp`` kernel it is benchmarked against.

What differs per window is the per-pair arithmetic the tile pays for:

  * ``exact``  — a base-e ``exp`` per synapse (the O(n²) transcendental
                 the intrinsic-timing register read eliminates);
  * ``linear`` — a PWL multiply+clip per synapse;
  * ``imstdp`` — a LUT read per synapse: the table lives in **SMEM**
                 (one scalar row per valid delay, built host-side by
                 ``ref.window_lut``) and is applied as a depth-long
                 select chain over the integer delay grid — scalar reads,
                 no vector gather.

Layout choices (mirroring the dense ``itp_stdp`` kernel): counters arrive
as ``(1, T)`` uint8 words with the neuron axis on the 128-wide lane
dimension; the weight tile stays resident in VMEM for the fused RMW; the
conv variant contracts the patch-row axis on the MXU with the same
accumulate-into-out_ref schedule as ``itp_stdp_conv``.

Counter rules are nearest-neighbour by construction (one counter holds
one spike time), so there is no pairing switch here.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.itp_counter.ref import window_exact, window_linear, window_lut


def counter_delays(words: jax.Array, depth: int) -> tuple[jax.Array, jax.Array]:
    """In-register Δt formation: uint8 counter words → (delays, validity).

    A word at value t means the neuron last spiked t steps ago; words
    saturate at ``depth`` (one past the last valid delay), so the validity
    gate is ``t <= depth - 1``.  Every kernel body routes through this —
    the round-trip (counter → word → in-register delay + validity) is
    pinned by the property tests in tests/test_counter_backend.py.
    """
    t = words.astype(jnp.int32)
    return t, (t <= depth - 1).astype(jnp.float32)


def _pair_window(
    dt: jax.Array,
    valid: jax.Array,
    lut_ref,
    lut_row: int,
    *,
    window: str,
    amplitude: float,
    tau: float,
    depth: int,
) -> jax.Array:
    """Per-pair window magnitude on an integer-delay tile, validity-gated.

    ``dt``/``valid`` are the broadcast (tile-shaped) delay and validity —
    the window is evaluated once per synapse, which is the measured-cost
    contract of the counter datapath (benchmarks/rule_cost.py).
    """
    # exact/linear evaluate the shared ref.py callables in the kernel body
    # (plain jnp, so they trace under Pallas) — ref.py stays the single
    # owner of the window semantics; only the imstdp SMEM read diverges
    # from its LUT-gather reference by construction
    if window == "exact":
        mag = window_exact(dt.astype(jnp.float32), amplitude, tau, depth)
    elif window == "linear":
        mag = window_linear(dt.astype(jnp.float32), amplitude, tau, depth)
    elif window == "imstdp":
        # SMEM LUT read: a depth-long select chain over the integer grid —
        # each step reads one scalar lut_ref[lut_row, k] from SMEM and
        # selects it where the pair's delay matches
        mag = jnp.zeros(dt.shape, jnp.float32)
        for k in range(depth):
            mag = jnp.where(dt == k, lut_ref[lut_row, k], mag)
    else:
        raise ValueError(f"unknown counter window {window!r}")
    return mag * valid


def _counter_stdp_kernel(
    pre_spike_ref,
    post_spike_ref,
    pre_word_ref,
    post_word_ref,
    lut_ref,
    w_ref,
    out_ref,
    *,
    depth: int,
    window: str,
    a_plus: float,
    a_minus: float,
    tau_plus: float,
    tau_minus: float,
    eta: float,
    w_min: float,
    w_max: float,
):
    tp = pre_word_ref.shape[1]
    tq = post_word_ref.shape[1]
    pre_t, pre_valid = counter_delays(pre_word_ref[...], depth)  # (1, TP)
    post_t, post_valid = counter_delays(post_word_ref[...], depth)  # (1, TQ)

    # per-pair Δt: broadcast the counter words across the synapse tile —
    # LTP pairs read the presynaptic delay, LTD pairs the postsynaptic one;
    # the pre side turns into a (TP, 1) column by a transpose of its row
    dt_ltp = jnp.broadcast_to(jnp.transpose(pre_t), (tp, tq))
    dt_ltd = jnp.broadcast_to(post_t, (tp, tq))
    ltp_mag = _pair_window(
        dt_ltp,
        jnp.broadcast_to(jnp.transpose(pre_valid), (tp, tq)),
        lut_ref,
        0,
        window=window,
        amplitude=a_plus,
        tau=tau_plus,
        depth=depth,
    )
    ltd_mag = _pair_window(
        dt_ltd,
        jnp.broadcast_to(post_valid, (tp, tq)),
        lut_ref,
        1,
        window=window,
        amplitude=a_minus,
        tau=tau_minus,
        depth=depth,
    )

    # XOR/AND control logic (§V-A), arithmetic form on {0,1}
    pre_col = jnp.transpose(pre_spike_ref[...].astype(jnp.float32))  # (TP, 1)
    post_s = post_spike_ref[...].astype(jnp.float32)  # (1, TQ)
    xor = pre_col + post_s - 2.0 * pre_col * post_s
    ltp_en = xor * post_s  # post fired alone
    ltd_en = xor * pre_col  # pre fired alone

    dw = ltp_en * ltp_mag - ltd_en * ltd_mag
    out_ref[...] = jnp.clip(w_ref[...] + eta * dw, w_min, w_max)


@functools.partial(
    jax.jit,
    static_argnames=(
        "depth",
        "window",
        "a_plus",
        "a_minus",
        "tau_plus",
        "tau_minus",
        "eta",
        "w_min",
        "w_max",
        "tile_pre",
        "tile_post",
        "interpret",
    ),
)
def counter_stdp_update(
    w: jax.Array,
    pre_spike: jax.Array,
    post_spike: jax.Array,
    pre_words: jax.Array,
    post_words: jax.Array,
    *,
    depth: int,
    window: str,
    a_plus: float,
    a_minus: float,
    tau_plus: float,
    tau_minus: float,
    eta: float = 1.0,
    w_min: float = 0.0,
    w_max: float = 1.0,
    tile_pre: int = 256,
    tile_post: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """Fused explicit-Δt STDP weight update from per-neuron counter words.

    Args:
      w:          (n_pre, n_post) float32 synapse matrix.
      pre_spike:  (n_pre,)  current-step spikes {0,1}.
      post_spike: (n_post,) current-step spikes {0,1}.
      pre_words:  (n_pre,)  uint8 last-spike counter words (t steps since
                  the last spike, saturated at ``depth``).
      post_words: (n_post,) uint8 counter words.
      depth:      history window — delays ``0..depth-1`` are live, the
                  saturated word value ``depth`` is gated to zero.
      window:     'exact' | 'linear' | 'imstdp' (see module docstring).
      a_plus/a_minus/tau_plus/tau_minus: the STDP window parameters.
      interpret:  run the kernel body in interpret mode (CPU validation);
                  the default False targets real accelerator hardware.

    Returns the updated, clipped weight matrix.
    """
    n_pre, n_post = w.shape
    tp = min(tile_pre, n_pre)
    tq = min(tile_post, n_post)
    if n_pre % tp or n_post % tq:
        raise ValueError(f"tile sizes ({tp},{tq}) must divide ({n_pre},{n_post})")

    lut = jnp.stack([window_lut(a_plus, tau_plus, depth), window_lut(a_minus, tau_minus, depth)])
    grid = (n_pre // tp, n_post // tq)
    kern = functools.partial(
        _counter_stdp_kernel,
        depth=depth,
        window=window,
        a_plus=a_plus,
        a_minus=a_minus,
        tau_plus=tau_plus,
        tau_minus=tau_minus,
        eta=eta,
        w_min=w_min,
        w_max=w_max,
    )
    return pl.pallas_call(
        kern,
        name="counter_stdp_update",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tp), lambda i, j: (0, i)),  # pre_spike
            pl.BlockSpec((1, tq), lambda i, j: (0, j)),  # post_spike
            pl.BlockSpec((1, tp), lambda i, j: (0, i)),  # pre counter words
            pl.BlockSpec((1, tq), lambda i, j: (0, j)),  # post counter words
            # window LUT: scalar rows in SMEM
            pl.BlockSpec((2, depth), lambda i, j: (0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((tp, tq), lambda i, j: (i, j)),  # w
        ],
        out_specs=pl.BlockSpec((tp, tq), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n_pre, n_post), jnp.float32),
        interpret=interpret,
    )(
        pre_spike.reshape(1, n_pre).astype(jnp.float32),
        post_spike.reshape(1, n_post).astype(jnp.float32),
        pre_words.reshape(1, n_pre).astype(jnp.uint8),
        post_words.reshape(1, n_post).astype(jnp.uint8),
        lut.astype(jnp.float32),
        w.astype(jnp.float32),
    )


def _counter_conv_kernel(
    pre_ref,
    post_ref,
    pre_word_ref,
    post_word_ref,
    lut_ref,
    out_ref,
    *,
    depth: int,
    window: str,
    a_plus: float,
    a_minus: float,
    tau_plus: float,
    tau_minus: float,
):
    pre = pre_ref[...].astype(jnp.float32)  # (TM, K)
    post = post_ref[...].astype(jnp.float32)  # (TM, C)
    pre_t, pre_valid = counter_delays(pre_word_ref[...], depth)  # (TM, K)
    post_t, post_valid = counter_delays(post_word_ref[...], depth)  # (TM, C)

    # per-(patch element) window evaluation — each element pays the window
    # arithmetic before the pair-gated patch-row contraction, mirroring the
    # dense kernel's per-pair cost on the im2col layout
    ltp_mag = _pair_window(
        pre_t,
        pre_valid,
        lut_ref,
        0,
        window=window,
        amplitude=a_plus,
        tau=tau_plus,
        depth=depth,
    )
    ltd_mag = _pair_window(
        post_t,
        post_valid,
        lut_ref,
        1,
        window=window,
        amplitude=a_minus,
        tau=tau_minus,
        depth=depth,
    )

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


@functools.partial(
    jax.jit,
    static_argnames=(
        "depth",
        "window",
        "a_plus",
        "a_minus",
        "tau_plus",
        "tau_minus",
        "tile_m",
        "interpret",
    ),
)
def counter_conv_delta(
    pre_patches: jax.Array,
    post_spikes: jax.Array,
    pre_words: jax.Array,
    post_words: jax.Array,
    *,
    depth: int,
    window: str,
    a_plus: float,
    a_minus: float,
    tau_plus: float,
    tau_minus: float,
    tile_m: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Patch-level fused explicit-Δt STDP conv weight delta.

    Args:
      pre_patches: (M, K) im2col spike patches, M = batch x output positions.
      post_spikes: (M, C) current-step output spikes.
      pre_words:   (M, K) uint8 counter words in the same im2col patch
                   layout as ``pre_patches`` (window readout commutes with
                   the gather — each element carries its source pixel's
                   last-spike delay).
      post_words:  (M, C) uint8 output-neuron counter words.
      depth/window/a_plus/a_minus/tau_plus/tau_minus: as in
                   :func:`counter_stdp_update`.
      tile_m:      patch rows per grid step; must divide M.
      interpret:   run through the Pallas interpreter (CPU validation).

    Returns the (K, C) float32 delta accumulated over all M patch rows.
    """
    m, kk = pre_patches.shape
    cc = post_spikes.shape[1]
    tm = min(tile_m, m)
    if m % tm:
        raise ValueError(f"tile_m={tm} must divide M={m}")

    lut = jnp.stack([window_lut(a_plus, tau_plus, depth), window_lut(a_minus, tau_minus, depth)])
    kern = functools.partial(
        _counter_conv_kernel,
        depth=depth,
        window=window,
        a_plus=a_plus,
        a_minus=a_minus,
        tau_plus=tau_plus,
        tau_minus=tau_minus,
    )
    return pl.pallas_call(
        kern,
        name="counter_conv_delta",
        grid=(m // tm,),
        in_specs=[
            pl.BlockSpec((tm, kk), lambda i: (i, 0)),  # pre patches
            pl.BlockSpec((tm, cc), lambda i: (i, 0)),  # post spikes
            pl.BlockSpec((tm, kk), lambda i: (i, 0)),  # pre counter words
            pl.BlockSpec((tm, cc), lambda i: (i, 0)),  # post counter words
            # window LUT: scalar rows in SMEM
            pl.BlockSpec((2, depth), lambda i: (0, 0), memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((kk, cc), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((kk, cc), jnp.float32),
        interpret=interpret,
    )(
        pre_patches.astype(jnp.float32),
        post_spikes.astype(jnp.float32),
        pre_words.astype(jnp.uint8),
        post_words.astype(jnp.uint8),
        lut.astype(jnp.float32),
    )
