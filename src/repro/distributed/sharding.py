"""Sharding rules: logical-axis specs → mesh PartitionSpecs for every arch.

Scheme (DESIGN.md §6):
  * batch        → ('pod', 'data') when a pod axis exists, else ('data',)
  * TP ('tp')    → 'model'  (heads / d_ff / vocab / d_inner)
  * FSDP ('fsdp')→ 'data'   (second weight dim, ZeRO-3 style)
  * experts      → 'model' when E divides the axis (EP), else TP inside
                   each expert (decided per arch by the divisibility guard)
  * sequence     → 'model' for long-context KV caches (serve-time SP)

Every rule passes a divisibility guard: an axis that does not divide the
dim is dropped (GSPMD could pad, but deliberate replication beats silent
padding + resharding churn).  ``constrain`` applies activation constraints
only when a mesh is active, so the same model code runs unsharded on CPU
tests.
"""
from __future__ import annotations

import contextlib
import re
import threading
from typing import Any, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_state = threading.local()


def shard_map_compat(f, *, mesh, in_specs, out_specs, axis_names=None):
    """``jax.shard_map`` with replication checking off; ``axis_names``
    selects the manual axes of a partial-manual map.  The single shim
    every shard_map call site (``repro.core.engine_sharded``, the
    multi-pod train step, the multi-device subprocess tests) routes
    through, so the call convention lives in exactly one place.
    """
    kw = {"check_vma": False}
    if axis_names is not None:
        kw["axis_names"] = axis_names
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kw)


def current_mesh() -> Mesh | None:
    return getattr(_state, "mesh", None)


def sharding_profile() -> str:
    """Parallelism profile for weights/activations.

    * 'fsdp'       — ZeRO-3: weights sharded over 'data', TP over 'model'
                     (the default; right for ≥10B models).
    * 'replicated' — DP+TP: weights replicated over 'data'; kills FSDP
                     weight gathers at the cost of HBM.
    * 'dp'         — pure data parallelism: weights fully replicated,
                     batch sharded over ('data','model') jointly.  For
                     sub-1B models the per-layer TP activation
                     all-reduces dominate the collective term (§Perf
                     cell 1); pure DP trades them for one gradient
                     all-reduce (0.6B f32 ⇒ 2.4 GB) — a ~20× predicted
                     reduction, affordable whenever params+opt fit HBM.
    * 'dp_zero3'   — pure-DP compute with weights/opt sharded over the
                     (compute-idle) 'model' axis, gathered on use: the
                     HBM-fitting variant of 'dp' (replicated state 7.2 GB
                     → 0.45 GB for qwen3-0.6b) at the cost of per-layer
                     weight all-gathers (≈ params bytes per pass).
    """
    return getattr(_state, "profile", "fsdp")


@contextlib.contextmanager
def use_sharding_profile(profile: str):
    prev = sharding_profile()
    _state.profile = profile
    try:
        yield
    finally:
        _state.profile = prev


@contextlib.contextmanager
def use_mesh(mesh: Mesh | None):
    prev = current_mesh()
    _state.mesh = mesh
    try:
        if mesh is not None:
            with mesh:   # Mesh is a context manager (thread-resources env)
                yield mesh
        else:
            yield None
    finally:
        _state.mesh = prev


def batch_axes(mesh: Mesh) -> tuple[str, ...]:
    if sharding_profile() in ("dp", "dp_zero3"):
        # pure DP: the model axis carries batch too
        return (("pod", "data", "model") if "pod" in mesh.axis_names
                else ("data", "model"))
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def _resolve_axis(logical, mesh: Mesh):
    """logical axis name → physical mesh axis (or tuple), or None."""
    if logical is None:
        return None
    if logical == "batch":
        return batch_axes(mesh)
    profile = sharding_profile()
    if logical == "tp":
        return None if profile in ("dp", "dp_zero3") else "model"
    if logical == "fsdp":
        if profile == "fsdp":
            return "data"
        if profile == "dp_zero3":
            return "model"
        return None
    return logical


def _axis_size(ax, mesh: Mesh) -> int:
    if isinstance(ax, tuple):
        n = 1
        for a in ax:
            n *= mesh.shape[a]
        return n
    return mesh.shape[ax]


def _manual_axes() -> frozenset:
    """Mesh axes that are Manual in the ambient abstract mesh.

    Inside a partial-manual ``shard_map`` (e.g. manual over 'pod' in the
    multi-pod train step) activation constraints must not mention the
    manual axes — the local shard has no pod dimension.
    """
    am = jax.sharding.get_abstract_mesh()
    return frozenset(
        name for name, t in zip(am.axis_names, am.axis_types)
        if t == jax.sharding.AxisType.Manual)


def _strip_manual(ax, manual):
    if ax is None:
        return None
    if isinstance(ax, tuple):
        kept = tuple(a for a in ax if a not in manual)
        return kept if kept else None
    return None if ax in manual else ax


def logical_to_spec(spec: Sequence, shape: tuple[int, ...],
                    mesh: Mesh) -> P:
    """Right-aligned logical spec → PartitionSpec with divisibility guard.

    ``spec`` names the trailing dims; leading (layer-stack) dims replicate.
    """
    spec = tuple(spec)
    if len(spec) > len(shape):
        spec = spec[len(spec) - len(shape):]
    pad = len(shape) - len(spec)
    manual = _manual_axes()
    out = [None] * pad
    for dim, logical in zip(shape[pad:], spec):
        ax = _strip_manual(_resolve_axis(logical, mesh), manual)
        if ax is not None and dim % _axis_size(ax, mesh) != 0:
            ax = None
        out.append(ax)
    return P(*out)


# ---------------------------------------------------------------------------
# Parameter rules
# ---------------------------------------------------------------------------

# ordered (regex on '/'-joined path, logical spec for the trailing dims)
_PARAM_RULES: list[tuple[str, tuple]] = [
    (r"embed/tok$", ("tp", "fsdp")),
    (r"embed/out$", ("fsdp", "tp")),
    (r"attn/wq$", ("fsdp", "tp")),
    (r"attn/wk$", ("fsdp", "tp")),
    (r"attn/wv$", ("fsdp", "tp")),
    (r"attn/wo$", ("tp", "fsdp")),
    (r"attn/b[qkv]$", ("tp",)),
    (r"mlp/(gate|up)$", ("fsdp", "tp")),
    (r"mlp/down$", ("tp", "fsdp")),
    (r"mlp/up_bias$", ("tp",)),
    (r"moe/router$", ("fsdp", None)),
    (r"moe/(gate|up)$", ("ep", "fsdp", "tp")),     # resolved per arch below
    (r"moe/down$", ("ep", "tp", "fsdp")),
    (r"shared/(gate|up)$", ("fsdp", "tp")),
    (r"shared/down$", ("tp", "fsdp")),
    (r"shared/route$", (None, None)),
    (r"ssm/wz$", ("fsdp", "tp")),
    (r"ssm/wxbc$", ("fsdp", "tp")),
    (r"ssm/wdt$", ("fsdp", None)),
    (r"ssm/conv_w$", (None, "tp")),
    (r"ssm/conv_b$", ("tp",)),
    (r"ssm/norm_scale$", ("tp",)),
    (r"ssm/out_proj$", ("tp", "fsdp")),
]


def _path_str(path) -> str:
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            parts.append(str(k))
    return "/".join(parts)


def param_spec_for(path_str: str, shape: tuple[int, ...], cfg,
                   mesh: Mesh) -> P:
    for pattern, spec in _PARAM_RULES:
        if re.search(pattern, path_str):
            if pattern == r"embed/tok$" and "pod" in mesh.axis_names:
                # XLA SPMD-partitioner workaround (verified crash,
                # spmd_partitioner_util.cc Check failure): a gather from a
                # table sharded on the *auto* 'data' axis inside a region
                # that is *manual* over 'pod' miscomputes its device
                # groups.  Dropping the fsdp factor on the token table
                # (keeping TP over vocab) sidesteps it; worst case
                # (qwen1.5-32b) costs 585 MB/device of replicated
                # embedding+opt state — well within HBM.
                spec = ("tp", None)
            if "ep" in spec:
                # expert-parallel when E (padded) divides the model axis,
                # else the expert dim replicates and TP shards inside
                if cfg.experts_alloc % mesh.shape["model"] == 0:
                    # EP: experts on 'model'; inner dims FSDP-only
                    spec = tuple("tp" if s == "ep" else
                                 (None if s == "tp" else s) for s in spec)
                else:
                    spec = tuple(None if s == "ep" else s for s in spec)
            return logical_to_spec(spec, shape, mesh)
    return P()  # norms, scalars, small vectors: replicate


def param_shardings(cfg, params, mesh: Mesh):
    """Pytree of NamedShardings matching ``params``."""
    def one(path, leaf):
        spec = param_spec_for(_path_str(path), leaf.shape, cfg, mesh)
        return NamedSharding(mesh, spec)
    return jax.tree_util.tree_map_with_path(one, params)


def param_spec_tree(cfg, params_shape, mesh: Mesh):
    def one(path, leaf):
        return param_spec_for(_path_str(path), leaf.shape, cfg, mesh)
    return jax.tree_util.tree_map_with_path(one, params_shape)


# ---------------------------------------------------------------------------
# Activation constraints (no-ops without an active mesh)
# ---------------------------------------------------------------------------

def constrain(x: jax.Array, spec: Sequence) -> jax.Array:
    mesh = current_mesh()
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, logical_to_spec(spec, x.shape, mesh))


def batch_spec(mesh: Mesh, ndim: int, *, seq_axis=None) -> P:
    """(B, ...) arrays: batch over ('pod','data'); optional seq over model."""
    out: list[Any] = [batch_axes(mesh)] + [None] * (ndim - 1)
    if seq_axis is not None:
        out[seq_axis] = "model"
    return P(*out)


# ---------------------------------------------------------------------------
# Decode-cache shardings (serving)
# ---------------------------------------------------------------------------

def _div(dim: int, ax, mesh: Mesh) -> bool:
    return ax is not None and dim % _axis_size(ax, mesh) == 0


def _batch_ax(dim: int, mesh: Mesh):
    """Largest batch sharding ('pod','data') → ('data',) → None that divides."""
    full = batch_axes(mesh)
    if _div(dim, full, mesh):
        return full
    if _div(dim, ("data",), mesh):
        return ("data",)
    return None


def kv_cache_spec(shape: tuple[int, ...], mesh: Mesh) -> P:
    """(L, B, T, K, hd) KV cache (or (L,B,T,K,1) scale) sharding.

    Preference order: KV heads on 'model' (TP-aligned with the attention
    projections); if the head count does not divide, fall back to sequence
    parallelism — shard the context axis T on 'model' (softmax reductions
    over T become GSPMD psums).  Batch goes over ('pod','data') when it
    divides, which it does for decode_32k (128) but not long_500k (1);
    there T additionally shards over 'data'.
    """
    L, B, T, K = shape[:4]
    b_ax = _batch_ax(B, mesh)
    k_ax = "model" if _div(K, "model", mesh) else None
    t_ax = None
    if k_ax is None and _div(T, ("model",), mesh):
        t_ax = ("model",)
    if b_ax is None:
        # latency-mode decode (B=1): spread the context over 'data' too
        if t_ax == ("model",) and _div(T, ("data", "model"), mesh):
            t_ax = ("data", "model")
        elif t_ax is None and _div(T, ("data",), mesh):
            t_ax = ("data",)
    rest = [None] * (len(shape) - 4)
    return P(None, b_ax, t_ax, k_ax, *rest)


def ssm_cache_specs(conv_shape: tuple[int, ...], state_shape: tuple[int, ...],
                    mesh: Mesh) -> tuple[P, P]:
    """SSM decode caches: conv (L,B,W,conv_dim), state (L,B,g,r,N,P).

    conv_dim and the head axis r align with the TP sharding of wxbc /
    the SSD head grouping, so both shard on 'model' when divisible.
    """
    Lb, B, W, conv_dim = conv_shape
    b_ax = _batch_ax(B, mesh)
    conv_spec = P(None, b_ax, None,
                  "model" if _div(conv_dim, "model", mesh) else None)
    _, Bs, g, r = state_shape[:4]
    r_ax = "model" if _div(r, "model", mesh) else None
    state_spec = P(None, _batch_ax(Bs, mesh), None, r_ax, None, None)
    return conv_spec, state_spec


def decode_cache_shardings(cache, mesh: Mesh):
    """NamedSharding pytree matching a DecodeCache (of arrays or SDS)."""
    def ns(spec):
        return NamedSharding(mesh, spec)

    def kv_shardings(kv):
        if kv is None:
            return None
        out = type(kv)(
            k=ns(kv_cache_spec(kv.k.shape, mesh)),
            v=ns(kv_cache_spec(kv.v.shape, mesh)),
            k_scale=(ns(kv_cache_spec(kv.k_scale.shape, mesh))
                     if kv.k_scale is not None else None),
            v_scale=(ns(kv_cache_spec(kv.v_scale.shape, mesh))
                     if kv.v_scale is not None else None),
        )
        return out

    def ssm_shardings(ssm):
        if ssm is None:
            return None
        conv_spec, state_spec = ssm_cache_specs(ssm.conv.shape,
                                                ssm.state.shape, mesh)
        return type(ssm)(conv=ns(conv_spec), state=ns(state_spec))

    def cross_sharding(x):
        if x is None:
            return None
        # (n_cross, B, Nv, K, hd)
        _, B, Nv, K = x.shape[:4]
        return ns(P(None, _batch_ax(B, mesh), None,
                    "model" if _div(K, "model", mesh) else None, None))

    return type(cache)(
        kv=kv_shardings(cache.kv),
        global_kv=kv_shardings(cache.global_kv),
        ssm=ssm_shardings(cache.ssm),
        cross_k=cross_sharding(cache.cross_k),
        cross_v=cross_sharding(cache.cross_v),
    )
