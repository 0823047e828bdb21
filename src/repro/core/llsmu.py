"""LLSMu — Logarithmic Linear Segmented Multiply (paper §II-D, eqs. 6-14).

Karatsuba decomposition of a 2N×2N-bit multiply into three N(+1)-bit
multiplies, each evaluated with the Mitchell logarithmic approximation with
the minimally-biased error-compensation constant c = 0.08333 [32].

Two datapaths are provided:

* :func:`mitchell_fixed` / :func:`llsmu_fixed` — **integer fixed-point**, a
  faithful model of the hardware datapath (Q-format mantissas, truncating
  shifts).  This is the oracle for the Pallas kernel.
* :func:`mitchell_float` — float shadow used for error analysis only.

Note on eq. (7): as typeset, the δ≥1 branch lacks the ×2 radix correction
(the true product lies in [2·2^(kx+ky), 4·2^(kx+ky)) there).  We implement
the standard minimally-biased form  2^(kx+ky+1)·(δ + c/2), which is
continuous with the δ<1 branch at δ=1 (both give 2^(kx+ky)(2+c)) and matches
[32]; docs/architecture.md §7 records this as a presumed typo.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

C_COMP = 0.08333  # error-compensation constant (paper §II-D)


def floor_log2(x: jax.Array, max_bits: int = 18) -> jax.Array:
    """Exact ⌊log2 x⌋ for non-negative int32 x (0 maps to 0).

    Implemented as a threshold count so it is exact (no float rounding) and
    vectorises on the VPU: k = #{i : x >= 2^i} - 1.
    """
    x = jnp.asarray(x, jnp.int32)
    thresholds = (1 << jnp.arange(max_bits, dtype=jnp.int32))
    k = jnp.sum(x[..., None] >= thresholds, axis=-1) - 1
    return jnp.maximum(k, 0).astype(jnp.int32)


def _var_shift(mant: jax.Array, s: jax.Array) -> jax.Array:
    """mant · 2^s with truncation for negative s (hardware barrel shift)."""
    left = jnp.maximum(s, 0)
    right = jnp.maximum(-s, 0)
    return (mant << left) >> right


def mitchell_fixed(x: jax.Array, y: jax.Array, *, frac_bits: int = 12,
                   c: float = C_COMP) -> jax.Array:
    """Mitchell approximate multiply, integer Q(frac_bits) datapath (eq. 7-9).

    Operands: non-negative int32 (intended ≤ ~9 bits so all intermediates fit
    int32).  Returns the approximate product as int32.
    """
    x = jnp.asarray(x, jnp.int32)
    y = jnp.asarray(y, jnp.int32)
    one = jnp.int32(1 << frac_bits)
    cq = jnp.int32(round(c * (1 << frac_bits)))

    kx = floor_log2(x)
    ky = floor_log2(y)
    # mantissas x/2^kx, y/2^ky in Q(frac_bits) — truncating, as in hardware
    fx = _var_shift(x, frac_bits - kx)
    fy = _var_shift(y, frac_bits - ky)
    delta = fx + fy - 2 * one                       # δ in Q(frac_bits)

    mant_lt = one + delta + cq                      # (1 + δ + c)
    mant_ge = 2 * (delta + cq // 2)                 # 2·(δ + c/2)
    mant = jnp.where(delta < one, mant_lt, mant_ge)

    p = _var_shift(mant, kx + ky - frac_bits)
    return jnp.where((x == 0) | (y == 0), 0, p).astype(jnp.int32)


def mitchell_float(x: jax.Array, y: jax.Array, *, c: float = C_COMP) -> jax.Array:
    """Float shadow of :func:`mitchell_fixed` (no quantisation error)."""
    x = jnp.asarray(x, jnp.float32)
    y = jnp.asarray(y, jnp.float32)
    kx = jnp.floor(jnp.log2(jnp.maximum(x, 1.0)))
    ky = jnp.floor(jnp.log2(jnp.maximum(y, 1.0)))
    fx = x / jnp.exp2(kx) - 1.0
    fy = y / jnp.exp2(ky) - 1.0
    delta = fx + fy
    mant = jnp.where(delta < 1.0, 1.0 + delta + c, 2.0 * (delta + c / 2.0))
    p = jnp.exp2(kx + ky) * mant
    return jnp.where((x == 0) | (y == 0), 0.0, p)


def llsmu_fixed(a: jax.Array, b: jax.Array, *, n_bits: int = 4,
                frac_bits: int = 12, c: float = C_COMP) -> jax.Array:
    """LLSMu approximate multiply of two 2N-bit operands (eqs. 6, 10-14).

    Default N=4 → 8-bit × 8-bit, the paper's datapath width (Table V:
    neuron/weight bitwidth 8).  All three partial products go through
    :func:`mitchell_fixed`; recombination (eq. 13) is exact integer adds and
    shifts.  Operands larger than 2N bits are legal (Karatsuba only needs
    L < 2^N); the int32 recombination is exact while the true product stays
    below 2^31 — use n_bits=4 for ≤ ~12-bit operands.
    """
    a = jnp.asarray(a, jnp.int32)
    b = jnp.asarray(b, jnp.int32)
    mask = jnp.int32((1 << n_bits) - 1)
    ha, la = a >> n_bits, a & mask
    hb, lb = b >> n_bits, b & mask

    m0 = mitchell_fixed(la, lb, frac_bits=frac_bits, c=c)
    m1 = mitchell_fixed(ha, hb, frac_bits=frac_bits, c=c)
    m2 = mitchell_fixed(ha + la, hb + lb, frac_bits=frac_bits, c=c)
    s3 = m2 - m0 - m1                                # eq. 12
    return (m1 << (2 * n_bits)) + (s3 << n_bits) + m0  # eq. 13


def llsmu_signed(a: jax.Array, b: jax.Array, **kw) -> jax.Array:
    """Sign-magnitude wrapper (the neuron datapath multiplies signed V-E)."""
    a = jnp.asarray(a, jnp.int32)
    b = jnp.asarray(b, jnp.int32)
    sign = jnp.sign(a) * jnp.sign(b)
    return sign * llsmu_fixed(jnp.abs(a), jnp.abs(b), **kw)


def relative_error(a: jax.Array, b: jax.Array, **kw) -> jax.Array:
    """|LLSMu(a,b) − a·b| / max(1, a·b) — used by tests and benchmarks."""
    exact = jnp.asarray(a, jnp.int64) if False else jnp.asarray(a, jnp.float32) * jnp.asarray(b, jnp.float32)
    approx = llsmu_fixed(a, b, **kw).astype(jnp.float32)
    return jnp.abs(approx - exact) / jnp.maximum(jnp.abs(exact), 1.0)
