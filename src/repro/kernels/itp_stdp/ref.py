"""Pure-jnp oracle for the ITP-STDP kernel (mirrors repro.core.stdp)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _sample_delta(pre_s, post_s, pre_bits, post_bits, po2_ltp, po2_ltd, *,
                  nearest):
    """One sample's Δw from its spikes and (depth, n) registers."""
    if nearest:
        pre_bits = pre_bits * (jnp.cumsum(pre_bits, axis=0) == 1.0)
        post_bits = post_bits * (jnp.cumsum(post_bits, axis=0) == 1.0)

    ltp_mag = po2_ltp @ pre_bits    # (n_pre,)
    ltd_mag = po2_ltd @ post_bits   # (n_post,)

    pre_s = pre_s.astype(jnp.bool_)
    post_s = post_s.astype(jnp.bool_)
    fire_xor = jnp.logical_xor(pre_s[:, None], post_s[None, :])
    ltp_en = jnp.logical_and(fire_xor, post_s[None, :]).astype(jnp.float32)
    ltd_en = jnp.logical_and(fire_xor, pre_s[:, None]).astype(jnp.float32)
    return ltp_en * ltp_mag[:, None] - ltd_en * ltd_mag[None, :]


def itp_stdp_update_ref(w: jax.Array,
                        pre_spike: jax.Array, post_spike: jax.Array,
                        pre_hist: jax.Array, post_hist: jax.Array,
                        po2_ltp: jax.Array, po2_ltd: jax.Array,
                        *,
                        nearest: bool = True,
                        eta: float = 1.0,
                        w_min: float = 0.0,
                        w_max: float = 1.0) -> jax.Array:
    """Reference semantics of the fused kernel, shapes as in kernel.py.

    Spikes ``(B, n)`` with registers ``(depth, B, n)`` are a batch, whose
    per-sample Δw are summed; ``(n,)`` with ``(depth, n)`` is one sample.
    """
    n_pre, n_post = w.shape
    depth = pre_hist.shape[0]
    dw = jax.vmap(
        lambda p, q, pb, qb: _sample_delta(
            p, q, pb, qb, po2_ltp.astype(jnp.float32),
            po2_ltd.astype(jnp.float32), nearest=nearest),
        in_axes=(0, 0, 1, 1),
    )(pre_spike.reshape(-1, n_pre), post_spike.reshape(-1, n_post),
      pre_hist.reshape(depth, -1, n_pre).astype(jnp.float32),
      post_hist.reshape(depth, -1, n_post).astype(jnp.float32)).sum(axis=0)
    return jnp.clip(w.astype(jnp.float32) + eta * dw, w_min, w_max)
