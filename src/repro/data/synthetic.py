"""Procedurally generated datasets standing in for the paper's three
datasets (MNIST, Fashion-MNIST, motor rotor-fault).

MNIST/F-MNIST/the fault dataset are not available offline
(docs/architecture.md §7); these generators produce class-structured data
with the same shapes and dynamic range, so the *parity* experiments of
Table II (exact STDP vs ITP-STDP ± compensation under one protocol) remain
meaningful.

All generators are pure functions of a PRNG key — reproducible, and
`vmap`-/`scan`-friendly for streaming pipelines.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# Image-like datasets (digits / fashion stand-ins)
# ---------------------------------------------------------------------------

def _digit_prototypes(side: int, n_classes: int) -> jax.Array:
    """Deterministic stroke-pattern prototypes, one per class (c, side, side)."""
    yy, xx = jnp.meshgrid(jnp.linspace(-1, 1, side), jnp.linspace(-1, 1, side),
                          indexing="ij")
    protos = []
    for c in range(n_classes):
        ang = 2.0 * jnp.pi * c / n_classes
        # oriented bar + class-dependent ring: distinct, overlapping strokes;
        # MNIST-like contrast (strokes saturate near 1, background at 0 —
        # this also matches the short-ISI regime behind the paper's Fig. 6)
        bar = jnp.exp(-((xx * jnp.cos(ang) + yy * jnp.sin(ang)) ** 2) / 0.05)
        r = jnp.sqrt(xx ** 2 + yy ** 2)
        ring = jnp.exp(-((r - 0.3 - 0.4 * (c % 3) / 2.0) ** 2) / 0.02)
        protos.append(jnp.clip(1.8 * (0.7 * bar + 0.5 * ring), 0.0, 1.0))
    return jnp.stack(protos)


def synthetic_digits(key: jax.Array, n: int, *, side: int = 28,
                     n_classes: int = 10, noise: float = 0.08,
                     jitter: int = 2) -> tuple[jax.Array, jax.Array]:
    """MNIST stand-in: (n, side, side) float in [0,1], labels (n,) int32."""
    k_lbl, k_shift, k_noise = jax.random.split(key, 3)
    labels = jax.random.randint(k_lbl, (n,), 0, n_classes)
    protos = _digit_prototypes(side, n_classes)
    imgs = protos[labels]                                       # (n, s, s)
    # per-sample translation jitter
    shifts = jax.random.randint(k_shift, (n, 2), -jitter, jitter + 1)
    imgs = jax.vmap(lambda im, sh: jnp.roll(im, sh, axis=(0, 1)))(imgs, shifts)
    imgs = imgs + noise * jax.random.normal(k_noise, imgs.shape)
    imgs = jnp.clip(imgs, 0.0, 1.0)
    # sensor floor: true-zero background, as in MNIST (anti-aliased strokes
    # on exact-zero canvas) — matters for the ISI statistics of §IV-B
    return jnp.where(imgs < 0.12, 0.0, imgs), labels


def synthetic_fashion(key: jax.Array, n: int, *, side: int = 28,
                      n_classes: int = 10, noise: float = 0.2
                      ) -> tuple[jax.Array, jax.Array]:
    """Fashion-MNIST stand-in: textured silhouettes (higher-noise regime)."""
    k_lbl, k_tex, k_noise = jax.random.split(key, 3)
    labels = jax.random.randint(k_lbl, (n,), 0, n_classes)
    yy, xx = jnp.meshgrid(jnp.linspace(-1, 1, side), jnp.linspace(-1, 1, side),
                          indexing="ij")
    freqs = 2.0 + jnp.arange(n_classes, dtype=jnp.float32)      # per-class texture
    widths = 0.35 + 0.4 * (jnp.arange(n_classes) % 4) / 3.0
    f, w = freqs[labels], widths[labels]
    sil = (jnp.abs(xx)[None] < w[:, None, None]).astype(jnp.float32) \
        * (jnp.abs(yy)[None] < 0.8).astype(jnp.float32)
    tex = 0.7 + 0.3 * jnp.sin(f[:, None, None] * jnp.pi
                              * (xx[None] + yy[None])
                              + jax.random.uniform(k_tex, (n, 1, 1)) * jnp.pi)
    imgs = sil * tex + noise * jax.random.normal(k_noise, sil.shape) * sil
    imgs = jnp.clip(imgs, 0.0, 1.0)
    return jnp.where(imgs < 0.12, 0.0, imgs), labels


def synthetic_fault(key: jax.Array, n: int, *, length: int = 512,
                    channels: int = 2, n_classes: int = 4,
                    noise: float = 0.1) -> tuple[jax.Array, jax.Array]:
    """Motor fault stand-in: (n, length, channels) current/flux signals.

    Class structure follows the physics of rotor faults: a fundamental at
    f0 plus class-dependent sideband pairs (broken bar ≈ ±2sf0 sidebands,
    eccentricity ≈ rotational-frequency modulation, bearing ≈ impulsive
    bursts), healthy = fundamental only.
    """
    k_lbl, k_ph, k_noise, k_imp = jax.random.split(key, 4)
    labels = jax.random.randint(k_lbl, (n,), 0, n_classes)
    t = jnp.linspace(0.0, 1.0, length)
    f0 = 50.0
    phase = jax.random.uniform(k_ph, (n, 1, 1)) * 2 * jnp.pi
    tt = t[None, :, None]
    ch_shift = jnp.arange(channels)[None, None, :] * (jnp.pi / 2)  # flux lags current
    base = jnp.sin(2 * jnp.pi * f0 * tt + phase + ch_shift)

    lbl = labels[:, None, None]
    side = 0.4 * jnp.sin(2 * jnp.pi * (f0 - 4.0) * tt + phase + ch_shift) \
         + 0.4 * jnp.sin(2 * jnp.pi * (f0 + 4.0) * tt + phase + ch_shift)
    ecc = 0.5 * jnp.sin(2 * jnp.pi * 12.5 * tt + ch_shift) * base
    impulses = (jax.random.uniform(k_imp, (n, length, 1)) > 0.98) \
        .astype(jnp.float32) * 1.5
    sig = base \
        + jnp.where(lbl == 1, side, 0.0) \
        + jnp.where(lbl == 2, ecc, 0.0) \
        + jnp.where(lbl == 3, impulses, 0.0)
    sig = sig + noise * jax.random.normal(k_noise, sig.shape)
    return sig, labels
