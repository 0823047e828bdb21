"""One dispatch layer for every weight-update datapath.

Before this module existed, every consumer of a :class:`LearningRule`
re-implemented the same three-way branch: resolve the backend
(``reference | fused | fused_interpret | sparse``), pick the packed or
unpacked readout layout, and call the matching rule hook with the right
shape plumbing — once in the engine, once per shard_map tile in the
sharded engine, and three more times in the SNN layers (fc fused, fc
sparse, conv).  An :class:`UpdatePlan` owns that cross-product exactly
once:

  * :func:`make_plan` resolves a config (``EngineConfig`` /
    ``SNNConfig`` duck-type) into a static plan — rule object, backend
    flags, packed-readout selection, effective compensation — at trace
    time;
  * :meth:`UpdatePlan.update` is the dense engine update (fused kernel /
    event-driven with silent-step skip / reference rank-1 path, plus
    clip);
  * :meth:`UpdatePlan.tile_update` is the shard_map tile body (same
    three-way dispatch on tile-local operands, including the global→tile
    event-index translation);
  * :meth:`UpdatePlan.state_readout` / :meth:`UpdatePlan.readout_ndim` /
    :meth:`UpdatePlan.pre_events_crossing` produce the replicated views
    that cross shard_map and the partition-spec shape to ship them with;
  * :meth:`UpdatePlan.fc_delta` / :meth:`UpdatePlan.conv_delta` are the
    batched SNN layer deltas (raw Δw — the layer owns eta / batch
    normalisation / clip / quantise).

Consumers (``repro.core.engine``, ``repro.core.engine_sharded``,
``repro.models.snn``, and everything above them) call only this module;
the rule hooks themselves (``kernel_readout`` / ``*_from_readout``) are
an implementation seam between the plan and the kernel packages, called
nowhere else (lint rule R8 in ``repro.analysis.astlint``).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro import tracing
from repro.core.stdp import STDPParams, pair_gate
from repro.kernels.dispatch import (im2col_1d, im2col_2d, im2col_words_1d,
                                    im2col_words_2d, spike_events)
from repro.plasticity.base import LearningRule, resolve_rule_backend


@dataclasses.dataclass(frozen=True)
class UpdatePlan:
    """Static dispatch decisions for one (rule, backend, config) cell.

    Built once per trace by :func:`make_plan`; every method is pure and
    jit/vmap/shard_map friendly (all fields are Python statics except
    ``stdp``, whose leaves are floats baked into the trace).
    """

    rule: LearningRule
    backend: str
    use_kernel: bool       # fused / fused_interpret
    interpret: bool
    sparse: bool           # event-driven datapath
    packed: bool           # resolved packed-word selection (depth <= 8)
    depth: int
    pairing: str
    compensate: bool       # effective (rule-override-resolved) flag
    stdp: STDPParams
    eta: float
    w_min: float
    w_max: float
    max_events: int | None

    # -- readout views (shard_map crossing) -----------------------------

    def state_readout(self, state: Any) -> jax.Array:
        """The per-neuron view of the timing state that crosses shard_map.

        Kernel and sparse backends ship the rule's kernel layout (packed
        ``(n,)`` uint8 words by default — the paper's register file);
        the reference backend ships the dense float rows its magnitude
        read is defined on.
        """
        if self.use_kernel or self.sparse:
            return self.rule.kernel_readout(state, packed=self.packed)
        return self.rule.readout(state).astype(jnp.float32)

    def readout_ndim(self) -> int:
        """ndim of :meth:`state_readout` (1 = words → shard axis 0,
        2 = rows → shard axis 1), known before any state exists."""
        if self.use_kernel or self.sparse:
            return self.rule.kernel_readout_axes(packed=self.packed)
        return 2

    # -- session serialization (the serving "plasticity cache") ---------
    # The serving layer (repro.serve) keeps each user's timing state as
    # the rule's packed uint8 word planes and rehydrates them around
    # every batched step; like the kernel hooks, the rule methods behind
    # these (``serve_words`` / ``state_from_words``) are called only
    # here (lint rule R8) so serving code never touches a rule layout.

    def words_per_neuron(self) -> int:
        """Resident uint8 words per neuron of the serialized timing state
        (1 for the history/counter words, 2 for mstdp's history +
        eligibility pair) — the bytes-per-neuron the serving store and
        ``benchmarks/serve_cost.py`` account."""
        return self.rule.words_per_neuron()

    def init_words(self, n: int) -> tuple[jax.Array, ...]:
        """Serialized fresh timing state for a population of ``n``."""
        return self.session_words(self.rule.init_state(n, self.depth))

    def session_words(self, state: Any) -> tuple[jax.Array, ...]:
        """Canonical ``(n,)`` uint8 word planes of a timing state."""
        return self.rule.serve_words(state)

    def session_state(self, words: tuple[jax.Array, ...]) -> Any:
        """Rebuild a timing state whose continued trajectory bit-matches
        the state :meth:`session_words` serialized."""
        return self.rule.state_from_words(words, depth=self.depth)

    def pre_events_crossing(self, pre_spikes: jax.Array) -> jax.Array:
        """Replicated global pre-event index vector for shard_map.

        Sparse backend: the static-shape event list extracted once from
        the replicated pre spikes (each tile translates it locally, see
        :meth:`tile_update`).  Dense backends cross a zero-length vector.
        """
        if not self.sparse:
            return jnp.zeros((0,), jnp.int32)
        events, _ = spike_events(pre_spikes, self.max_events)
        return events

    # -- dense engine update --------------------------------------------

    def update(self, w: jax.Array, pre_spikes: jax.Array,
               post_spikes: jax.Array, pre_state: Any,
               post_state: Any) -> jax.Array:
        """Full clipped update of the dense ``(n_pre, n_post)`` matrix.

        The engine's step-3 datapath: fused Pallas RMW, event-driven
        gather/scatter with the silent-step skip (a step with no event on
        either side is identically zero through the XOR pair gate, so
        ``lax.cond`` skips it outright), or the reference rank-1 gated
        outer product + clip.
        """
        rule = self.rule
        if self.use_kernel:
            return rule.fused_update_from_readout(
                w, pre_spikes, post_spikes,
                rule.kernel_readout(pre_state, packed=self.packed),
                rule.kernel_readout(post_state, packed=self.packed),
                self.stdp, depth=self.depth, pairing=self.pairing,
                compensate=self.compensate, eta=self.eta, w_min=self.w_min,
                w_max=self.w_max, interpret=self.interpret)
        if self.sparse:
            pre_read = rule.kernel_readout(pre_state, packed=self.packed)
            post_read = rule.kernel_readout(post_state, packed=self.packed)

            def _sparse_update(w):
                return rule.sparse_update_from_readout(
                    w, pre_spikes, post_spikes, pre_read, post_read,
                    self.stdp, depth=self.depth, pairing=self.pairing,
                    compensate=self.compensate, eta=self.eta,
                    w_min=self.w_min, w_max=self.w_max,
                    max_events=self.max_events)

            any_event = jnp.any(pre_spikes != 0) | jnp.any(post_spikes)
            return jax.lax.cond(any_event, _sparse_update, lambda w: w, w)
        dw = rule.delta(pre_state, post_state, pre_spikes, post_spikes,
                        self.stdp, depth=self.depth, pairing=self.pairing,
                        compensate=self.compensate)
        return jnp.clip(w + self.eta * dw, self.w_min, self.w_max)

    # -- shard_map tile update ------------------------------------------

    def tile_update(self, w: jax.Array, pre_spikes: jax.Array,
                    post_spikes: jax.Array, pre_read: jax.Array,
                    post_read: jax.Array, *,
                    pre_events: jax.Array | None = None,
                    pre_axis: str | None = None) -> jax.Array:
        """Clipped update of one local ``(pre_tile, post_tile)`` tile.

        Same three-way dispatch as :meth:`update`, but on tile-local
        operands: the readout views arrive pre-sliced by shard_map, and
        for the sparse backend the replicated *global* event indices in
        ``pre_events`` are translated into this tile's row range
        (out-of-tile events map to the out-of-range sentinel ``tile`` so
        the ``mode="drop"`` scatters ignore them — negative indices would
        wrap, hence the explicit remap).
        """
        rule = self.rule
        if self.use_kernel:
            return rule.fused_update_from_readout(
                w, pre_spikes, post_spikes, pre_read, post_read, self.stdp,
                depth=self.depth, pairing=self.pairing,
                compensate=self.compensate, eta=self.eta, w_min=self.w_min,
                w_max=self.w_max, interpret=self.interpret)
        if self.sparse:
            tile = w.shape[0]
            local = pre_events
            if pre_axis is not None:
                start = jax.lax.axis_index(pre_axis) * tile
                local = pre_events - start
                local = jnp.where((local >= 0) & (local < tile), local, tile)
            return rule.sparse_update_from_readout(
                w, pre_spikes, post_spikes, pre_read, post_read, self.stdp,
                depth=self.depth, pairing=self.pairing,
                compensate=self.compensate, eta=self.eta, w_min=self.w_min,
                w_max=self.w_max, max_events=self.max_events,
                pre_events=local)
        ltp = rule.magnitudes_from_readout(
            pre_read, self.stdp.a_plus, self.stdp.tau_plus,
            depth=self.depth, pairing=self.pairing,
            compensate=self.compensate)
        ltd = rule.magnitudes_from_readout(
            post_read, self.stdp.a_minus, self.stdp.tau_minus,
            depth=self.depth, pairing=self.pairing,
            compensate=self.compensate)
        ltp_en, ltd_en = pair_gate(pre_spikes[:, None], post_spikes[None, :])
        dw = ltp_en * ltp[:, None] - ltd_en * ltd[None, :]
        return jnp.clip(w + self.eta * dw, self.w_min, self.w_max)

    # -- batched SNN layer deltas ---------------------------------------

    def _batched_readouts(self, pre_state: Any, post_state: Any,
                          batch: int) -> tuple[jax.Array, jax.Array]:
        """Batch-major kernel readout views for the fc paths.

        Word readouts ((B·n,) uint8 — packed register / counter words)
        reshape to ``(B, n)``; row readouts ((rows, B·n)) to
        ``(rows, B, n)`` (row count is rule-specific — ``depth``
        bitplanes for the history rules, one counter row, history+trace
        rows for composite-state rules).
        """
        pre_read = self.rule.kernel_readout(pre_state, packed=self.packed)
        post_read = self.rule.kernel_readout(post_state, packed=self.packed)
        if pre_read.ndim == 1:
            return pre_read.reshape(batch, -1), post_read.reshape(batch, -1)
        return (pre_read.reshape(pre_read.shape[0], batch, -1),
                post_read.reshape(post_read.shape[0], batch, -1))

    def fc_delta(self, pre_state: Any, post_state: Any, s_in: jax.Array,
                 s_out: jax.Array) -> jax.Array:
        """Batch-summed raw ``(fan_in, n_out)`` Δw for an fc layer.

        The fc layer is the engine's dense synapse matrix replicated over
        the batch: the fused backends hand the whole batch to the rule's
        delta hook once (the itp kernel contracts the batch rows into one
        Δw tile), the sparse backend vmaps its per-sample event hook and
        accumulates; the reference backend is the einsum form of the same
        pair-gated rank-1 update (P = 1 special case of the conv patch
        formula).  Raw delta — the layer owns eta / B normalisation /
        clip / quantise.
        """
        B = s_in.shape[0]
        pre = s_in.reshape(B, -1)                       # (B, fan_in)
        post = s_out.reshape(B, -1)                     # (B, n_out)
        if not (self.use_kernel or self.sparse):
            ltp = self.rule.magnitudes(
                pre_state, self.stdp.a_plus, self.stdp.tau_plus,
                depth=self.depth, pairing=self.pairing,
                compensate=self.compensate)
            ltd = self.rule.magnitudes(
                post_state, self.stdp.a_minus, self.stdp.tau_minus,
                depth=self.depth, pairing=self.pairing,
                compensate=self.compensate)
            ltp_p = ltp.reshape(B, 1, -1)               # (B, P=1, fan_in)
            pre_p = pre.reshape(B, 1, -1)
            post_s = post.reshape(B, 1, -1)
            ltd_m = ltd.reshape(B, 1, -1)
            # pair gate (§V-A): potentiate where post fired alone,
            # depress where pre fired alone
            dw_ltp = jnp.einsum("bpk,bpc->kc", (1.0 - pre_p) * ltp_p, post_s)
            dw_ltd = jnp.einsum("bpk,bpc->kc", pre_p, (1.0 - post_s) * ltd_m)
            return dw_ltp - dw_ltd
        with jax.named_scope(tracing.TIMING):
            pre_read, post_read = self._batched_readouts(pre_state, post_state, B)
        if not self.sparse:
            return self.rule.fused_delta_from_readout(
                pre, post, pre_read, post_read, self.stdp, depth=self.depth,
                pairing=self.pairing, compensate=self.compensate,
                interpret=self.interpret)

        def one(p, q, pr, qr):
            return self.rule.sparse_delta_from_readout(
                p, q, pr, qr, self.stdp, depth=self.depth,
                pairing=self.pairing, compensate=self.compensate,
                max_events=self.max_events)

        row_axis = 0 if pre_read.ndim == 2 else 1       # the batch axis
        return jax.vmap(one, in_axes=(0, 0, row_axis, row_axis))(
            pre, post, pre_read, post_read).sum(axis=0)

    def conv_delta(self, pre_state: Any, post_state: Any,
                   patches: jax.Array, s_out: jax.Array, *,
                   in_shape: tuple, kind: str, kernel: int,
                   stride: int) -> jax.Array:
        """Batch+position-summed raw ``(K, C)`` Δw for a conv layer.

        The conv STDP update is the dense pair rule per (patch element →
        output channel) synapse accumulated over batch and spatial
        positions; the timing readout is gathered into the same im2col
        layout as the spikes (readout commutes with the gather — each
        patch element carries its source pixel's timing state).  Packed
        word readouts gather once as ``(M, K)`` uint8; row readouts
        materialise ``(rows, M, ·)`` float patches (the oracle layout).
        Dispatches to the rule's sparse conv hook (``backend="sparse"``)
        or its conv kernel/oracle hook otherwise.
        """
        rule = self.rule
        B = s_out.shape[0]
        packed = self.use_kernel and self.packed
        with jax.named_scope(tracing.TIMING):
            pre_read = rule.kernel_readout(pre_state, packed=packed)
            post_read = rule.kernel_readout(post_state, packed=packed)
            if pre_read.ndim == 1:
                # per-neuron word readout: im2col the (M, K) uint8 words once
                im2col_w = im2col_words_2d if kind == "conv2d" else im2col_words_1d
                with jax.named_scope(tracing.WINDOW):
                    pre_read = im2col_w(pre_read.reshape((B,) + tuple(in_shape)),
                                        kernel, stride)
                    pre_read = pre_read.reshape(-1, pre_read.shape[-1])  # (M, K)
                post_read = post_read.reshape(-1, s_out.shape[-1])   # (M, C)
            else:
                # dense row layout: (rows, M, ·) float32 patches
                im2col = im2col_2d if kind == "conv2d" else im2col_1d
                rows = pre_read.shape[0]
                pre_read = pre_read.astype(jnp.float32)
                pre_read = pre_read.reshape((rows, B) + tuple(in_shape))
                pre_read = jax.vmap(
                    lambda p: im2col(p, kernel, stride))(pre_read)
                pre_read = pre_read.reshape(rows, -1, pre_read.shape[-1])
                post_read = post_read.astype(jnp.float32).reshape(
                    rows, -1, s_out.shape[-1])
        pre_patches = patches.reshape(-1, patches.shape[-1])         # (M, K)
        post_spikes = s_out.reshape(-1, s_out.shape[-1])             # (M, C)
        if self.sparse:
            return rule.sparse_conv_delta_from_readout(
                pre_patches, post_spikes, pre_read, post_read, self.stdp,
                depth=self.depth, pairing=self.pairing,
                compensate=self.compensate, max_events=self.max_events)
        return rule.conv_delta_from_readout(
            pre_patches, post_spikes, pre_read, post_read, self.stdp,
            depth=self.depth, pairing=self.pairing,
            compensate=self.compensate, use_kernel=self.use_kernel,
            interpret=self.interpret)


def make_plan(cfg: Any) -> UpdatePlan:
    """Resolve a config into an :class:`UpdatePlan`.

    Duck-typed over ``EngineConfig`` and ``SNNConfig``: both carry
    ``rule`` / ``backend`` / ``depth`` / ``pairing`` / ``stdp`` /
    ``eta`` / ``max_events`` plus ``learning_rule()`` and
    ``use_packed_history()``; the engine's clip window
    (``w_min``/``w_max``) defaults to the SNN's fixed [0, 1] when the
    config has none, and compensation resolves through
    ``effective_compensate()`` where available (EngineConfig) or the
    ``compensate`` property (SNNConfig).
    """
    rule = cfg.learning_rule()
    use_kernel, interpret = resolve_rule_backend(rule, cfg.backend)
    if hasattr(cfg, "effective_compensate"):
        compensate = cfg.effective_compensate()
    else:
        compensate = cfg.compensate
    return UpdatePlan(
        rule=rule,
        backend=cfg.backend,
        use_kernel=use_kernel,
        interpret=interpret,
        sparse=cfg.backend == "sparse",
        packed=cfg.use_packed_history(),
        depth=cfg.depth,
        pairing=cfg.pairing,
        compensate=compensate,
        stdp=cfg.stdp,
        eta=cfg.eta,
        w_min=getattr(cfg, "w_min", 0.0),
        w_max=getattr(cfg, "w_max", 1.0),
        max_events=cfg.max_events,
    )


def apply_update(cfg: Any, w: jax.Array, pre_spikes: jax.Array,
                 post_spikes: jax.Array, pre_state: Any,
                 post_state: Any) -> jax.Array:
    """One-shot convenience: :func:`make_plan` + :meth:`UpdatePlan.update`."""
    return make_plan(cfg).update(w, pre_spikes, post_spikes,
                                 pre_state, post_state)
