"""The registered learning rules: the paper's rule hierarchy as state
machines (curve-level forms live in ``repro.core.stdp``).

Two families, matching the paper's §I taxonomy:

  * :class:`HistoryRule` — intrinsic timing (this work).  State is the
    bitplane spike history; the timing difference is never computed: the
    register read *is* the update (eq. 2 / Fig. 3).  ``itp`` (compensated
    by default, eq. 18) and ``itp_nocomp`` (raw po2, §IV-A error bound).
    These are the rules the fused Pallas kernels implement.

  * :class:`CounterRule` — conventional explicit-Δt datapaths.  State is
    a per-neuron last-spike counter (saturating at ``depth``); on an
    update the per-pair timing difference is formed and a window function
    evaluated per synapse — the O(n²) transcendental work Tables III-V
    monetise.  ``exact`` (base-e exponential, [26]/[28]-style),
    ``linear`` (the PWL approximation of [24]) and ``imstdp`` (the
    integer-grid LUT of [23]).  The window semantics live in
    ``repro.kernels.itp_counter.ref`` (shared with the fused Pallas
    counter kernels, so the jnp reference and the kernel oracle cannot
    drift); the fused* backends run the same per-pair datapath on-chip
    style — Δt formed in-register from the counter word, window fused
    with the weight accumulate — which is what makes ``rule_cost`` the
    paper's kernel-vs-kernel speedup comparison.

A counter at value t means the neuron last spiked t steps ago (t=0: the
previous step — spikes are recorded *after* the weight update, exactly
like the history shift-in), so nearest-neighbour magnitudes agree with
the history read on the integer grid: ``exact`` with the same ``depth``
is trajectory-identical to compensated ``itp`` — the paper's equivalence
claim, pinned by tests/test_plasticity.py.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core import history as H
from repro.core.stdp import STDPParams, magnitudes_depth_major, pair_gate
from repro.kernels.itp_counter.ref import WINDOWS, counter_magnitudes
from repro.plasticity.base import LearningRule, register_rule


@dataclasses.dataclass(frozen=True)
class HistoryRule(LearningRule):
    """Intrinsic-timing po2 rule: bitplane-history state, register-read Δw."""

    name: str = "itp"
    has_kernel: bool = True
    has_sparse: bool = True
    compensate: bool | None = None  # None: defer to the config flag

    def init_state(self, n: int, depth: int) -> H.SpikeHistory:
        return H.init_history(n, depth)

    def step(self, state: H.SpikeHistory, spikes: jax.Array, *, depth: int) -> H.SpikeHistory:
        del depth  # state carries it
        return H.push(state, spikes)

    def readout(self, state: H.SpikeHistory) -> jax.Array:
        return H.registers_depth_major(state)  # (depth, n), k=0 newest

    def readout_packed(self, state: H.SpikeHistory) -> jax.Array:
        return H.pack_words(state)  # (n,) uint8, MSB = newest

    # -- session serialization: one history word per neuron -------------

    def words_per_neuron(self) -> int:
        return 1

    def serve_words(self, state: H.SpikeHistory) -> tuple[jax.Array, ...]:
        return (H.pack_words(state),)

    def state_from_words(self, words: tuple[jax.Array, ...], *, depth: int) -> H.SpikeHistory:
        (word,) = words
        return H.from_words(word, depth)

    def magnitudes_from_readout(
        self,
        arr: jax.Array,
        amplitude: float,
        tau: float,
        *,
        depth: int,
        pairing: str = "nearest",
        compensate: bool = True,
    ) -> jax.Array:
        # the rule's compensate override (itp_nocomp) is resolved once at
        # the config level (EngineConfig.effective_compensate /
        # SNNConfig.compensate) — callers pass the resolved flag
        del depth  # arr carries it
        return magnitudes_depth_major(arr, amplitude, tau, pairing=pairing, compensate=compensate)

    def last_spikes(self, state: H.SpikeHistory) -> jax.Array:
        # the newest spike bit is planes[head] directly — reading it via
        # as_register(state)[:, 0] would materialise the full (N, depth)
        # gather+transpose every step just to drop depth-1 columns
        # (equivalence pinned by tests/test_plasticity.py)
        return H.latest(state).astype(jnp.float32)

    # -- fused (kernel) datapath: the itp_stdp / itp_stdp_conv packages --

    def kernel_readout(self, state: H.SpikeHistory, *, packed: bool) -> jax.Array:
        return self.readout_packed(state) if packed else self.readout(state)

    def kernel_readout_axes(self, *, packed: bool) -> int:
        return 1 if packed else 2

    def fused_update_from_readout(
        self,
        w: jax.Array,
        pre_spike: jax.Array,
        post_spike: jax.Array,
        pre_read: jax.Array,
        post_read: jax.Array,
        p: STDPParams,
        *,
        depth: int,
        pairing: str = "nearest",
        compensate: bool = True,
        eta: float = 1.0,
        w_min: float = 0.0,
        w_max: float = 1.0,
        interpret: bool = False,
    ) -> jax.Array:
        # deferred import: repro.core must stay importable from the kernel
        # packages' own modules (ops.py imports repro.core.history)
        from repro.kernels.itp_stdp.ops import weight_update_depth_major, weight_update_packed

        kw = dict(
            pairing=pairing,
            compensate=compensate,
            eta=eta,
            w_min=w_min,
            w_max=w_max,
            interpret=interpret,
        )
        if pre_read.ndim == 1:  # packed uint8 register words
            return weight_update_packed(
                w, pre_spike, post_spike, pre_read, post_read, p, depth=depth, **kw
            )
        return weight_update_depth_major(w, pre_spike, post_spike, pre_read, post_read, p, **kw)

    def fused_delta_from_readout(
        self,
        pre_spike: jax.Array,
        post_spike: jax.Array,
        pre_read: jax.Array,
        post_read: jax.Array,
        p: STDPParams,
        *,
        depth: int,
        pairing: str = "nearest",
        compensate: bool = True,
        interpret: bool = False,
    ) -> jax.Array:
        from repro.kernels.itp_stdp.ops import synapse_delta, synapse_delta_packed

        # one kernel call contracts the whole batch into one Δw tile
        kw = dict(pairing=pairing, compensate=compensate, interpret=interpret)
        if pre_read.ndim == 2:  # (B, n) packed words (bitplanes are (depth, B, n))
            return synapse_delta_packed(
                pre_spike, post_spike, pre_read, post_read, p, depth=depth, **kw
            )
        return synapse_delta(pre_spike, post_spike, pre_read, post_read, p, **kw)

    def conv_delta_from_readout(
        self,
        pre_patches: jax.Array,
        post_spikes: jax.Array,
        pre_read: jax.Array,
        post_read: jax.Array,
        p: STDPParams,
        *,
        depth: int,
        pairing: str = "nearest",
        compensate: bool = True,
        use_kernel: bool = True,
        interpret: bool = False,
    ) -> jax.Array:
        from repro.kernels.itp_stdp_conv.ops import conv_synapse_delta, conv_synapse_delta_packed

        kw = dict(
            pairing=pairing,
            compensate=compensate,
            use_kernel=use_kernel,
            interpret=interpret,
        )
        if pre_read.ndim == 2:  # (M, K) packed words (bitplanes are (depth, M, K))
            return conv_synapse_delta_packed(
                pre_patches, post_spikes, pre_read, post_read, p, depth=depth, **kw
            )
        return conv_synapse_delta(pre_patches, post_spikes, pre_read, post_read, p, **kw)

    # -- event-driven (sparse) datapath: the itp_sparse package ---------

    def _readout_rows(self, arr: jax.Array, depth: int) -> jax.Array:
        """Normalise a readout view to (depth, n) registers, k=0 newest.

        Accepts either the packed uint8 word layout ((n,), the format
        that crosses shard_map) or the dense depth-major rows; unpacking
        is bit-exact, so both produce identical magnitudes.
        """
        if arr.ndim == 1:  # packed uint8 register words
            return H.unpack_words(arr, depth).T
        return arr

    def sparse_update_from_readout(
        self,
        w: jax.Array,
        pre_spike: jax.Array,
        post_spike: jax.Array,
        pre_read: jax.Array,
        post_read: jax.Array,
        p: STDPParams,
        *,
        depth: int,
        pairing: str = "nearest",
        compensate: bool = True,
        eta: float = 1.0,
        w_min: float = 0.0,
        w_max: float = 1.0,
        max_events: int | None = None,
        pre_events: jax.Array | None = None,
        post_events: jax.Array | None = None,
    ) -> jax.Array:
        from repro.kernels.itp_sparse.ops import sparse_weight_update

        kw = dict(depth=depth, pairing=pairing, compensate=compensate)
        ltp = self.magnitudes_from_readout(
            self._readout_rows(pre_read, depth), p.a_plus, p.tau_plus, **kw
        )
        ltd = self.magnitudes_from_readout(
            self._readout_rows(post_read, depth), p.a_minus, p.tau_minus, **kw
        )
        return sparse_weight_update(
            w,
            pre_spike,
            post_spike,
            ltp,
            ltd,
            eta=eta,
            w_min=w_min,
            w_max=w_max,
            max_events=max_events,
            pre_events=pre_events,
            post_events=post_events,
        )

    def sparse_delta_from_readout(
        self,
        pre_spike: jax.Array,
        post_spike: jax.Array,
        pre_read: jax.Array,
        post_read: jax.Array,
        p: STDPParams,
        *,
        depth: int,
        pairing: str = "nearest",
        compensate: bool = True,
        max_events: int | None = None,
    ) -> jax.Array:
        from repro.kernels.itp_sparse.ops import sparse_synapse_delta

        kw = dict(depth=depth, pairing=pairing, compensate=compensate)
        ltp = self.magnitudes_from_readout(
            self._readout_rows(pre_read, depth), p.a_plus, p.tau_plus, **kw
        )
        ltd = self.magnitudes_from_readout(
            self._readout_rows(post_read, depth), p.a_minus, p.tau_minus, **kw
        )
        return sparse_synapse_delta(pre_spike, post_spike, ltp, ltd, max_events=max_events)

    def sparse_conv_delta_from_readout(
        self,
        pre_patches: jax.Array,
        post_spikes: jax.Array,
        pre_read: jax.Array,
        post_read: jax.Array,
        p: STDPParams,
        *,
        depth: int,
        pairing: str = "nearest",
        compensate: bool = True,
        max_events: int | None = None,
    ) -> jax.Array:
        from repro.core.stdp import po2_weights
        from repro.kernels.itp_sparse.ops import sparse_conv_delta

        po2_ltp = p.a_plus * po2_weights(depth, p.tau_plus, compensate=compensate)
        po2_ltd = p.a_minus * po2_weights(depth, p.tau_minus, compensate=compensate)
        return sparse_conv_delta(
            pre_patches,
            post_spikes,
            pre_read,
            post_read,
            po2_ltp,
            po2_ltd,
            nearest=pairing == "nearest",
            max_events=max_events,
        )


@dataclasses.dataclass(frozen=True)
class CounterRule(LearningRule):
    """Conventional Δt-based STDP: last-spike counters + per-pair window.

    Nearest-neighbour only (one counter holds one spike time).  A counter
    saturates at ``depth`` (one past the last valid delay ``depth-1``),
    mirroring the finite history window of the po2 rules.  The fused*
    backends route to ``repro.kernels.itp_counter`` — the same per-pair
    window datapath run on-chip style (Δt broadcast in-register from the
    uint8 counter word, window fused with the weight accumulate), so the
    ``rule_cost`` comparison against the ITP kernels is kernel-vs-kernel.
    """

    name: str = "exact"
    window: str = "exact"
    has_kernel: bool = True
    compensate: bool | None = None

    def _window_fn(self):
        return WINDOWS[self.window]

    def init_state(self, n: int, depth: int) -> jax.Array:
        # start saturated-invalid: no spike within the window yet
        return jnp.full((n,), depth, jnp.int32)

    def step(self, state: jax.Array, spikes: jax.Array, *, depth: int) -> jax.Array:
        fired = jnp.asarray(spikes).astype(bool)
        return jnp.where(fired, 0, jnp.minimum(state + 1, depth)).astype(jnp.int32)

    def readout(self, state: jax.Array) -> jax.Array:
        return state.astype(jnp.float32)[None, :]  # (1, n)

    def readout_packed(self, state: jax.Array) -> jax.Array:
        # the saturating counter IS the word: one uint8 per neuron, the
        # same shape/sharding contract as the packed history words
        # (depth <= 255 so the saturation value always fits)
        return state.astype(jnp.uint8)

    # -- session serialization: the counter word round-trips losslessly -

    def words_per_neuron(self) -> int:
        return 1

    def serve_words(self, state: jax.Array) -> tuple[jax.Array, ...]:
        return (self.readout_packed(state),)

    def state_from_words(self, words: tuple[jax.Array, ...], *, depth: int) -> jax.Array:
        del depth  # counters saturate at depth but the word stores the value
        (word,) = words
        return word.astype(jnp.int32)

    def check_pairing(self, pairing: str) -> None:
        if pairing != "nearest":
            raise ValueError(
                f"rule {self.name!r} is counter-based (one last-spike time "
                f"per neuron) and supports pairing='nearest' only, got "
                f"{pairing!r}"
            )

    def magnitudes_from_readout(
        self,
        arr: jax.Array,
        amplitude: float,
        tau: float,
        *,
        depth: int,
        pairing: str = "nearest",
        compensate: bool = True,
    ) -> jax.Array:
        self.check_pairing(pairing)
        return counter_magnitudes(arr[0], amplitude, tau, depth=depth, window=self.window)

    def last_spikes(self, state: jax.Array) -> jax.Array:
        return (state == 0).astype(jnp.float32)

    # -- fused (kernel) datapath: the itp_counter package ---------------

    def kernel_readout(self, state: jax.Array, *, packed: bool) -> jax.Array:
        del packed  # one uint8 counter word per neuron is the only layout
        return self.readout_packed(state)

    def kernel_readout_axes(self, *, packed: bool) -> int:
        del packed
        return 1

    def fused_update_from_readout(
        self,
        w: jax.Array,
        pre_spike: jax.Array,
        post_spike: jax.Array,
        pre_read: jax.Array,
        post_read: jax.Array,
        p: STDPParams,
        *,
        depth: int,
        pairing: str = "nearest",
        compensate: bool = True,
        eta: float = 1.0,
        w_min: float = 0.0,
        w_max: float = 1.0,
        interpret: bool = False,
    ) -> jax.Array:
        from repro.kernels.itp_counter.ops import counter_weight_update

        self.check_pairing(pairing)
        del compensate  # counter windows read τ directly (no po2 read to fix)
        return counter_weight_update(
            w,
            pre_spike,
            post_spike,
            pre_read,
            post_read,
            p,
            depth=depth,
            window=self.window,
            eta=eta,
            w_min=w_min,
            w_max=w_max,
            interpret=interpret,
        )

    def fused_delta_from_readout(
        self,
        pre_spike: jax.Array,
        post_spike: jax.Array,
        pre_read: jax.Array,
        post_read: jax.Array,
        p: STDPParams,
        *,
        depth: int,
        pairing: str = "nearest",
        compensate: bool = True,
        interpret: bool = False,
    ) -> jax.Array:
        from repro.kernels.itp_counter.ops import counter_synapse_delta

        self.check_pairing(pairing)
        del compensate

        # the counter kernel takes one sample: map it over the (B, n) words
        def one(ps, qs, pw, qw):
            return counter_synapse_delta(
                ps, qs, pw, qw, p, depth=depth, window=self.window, interpret=interpret
            )

        return jax.vmap(one)(pre_spike, post_spike, pre_read, post_read).sum(axis=0)

    def conv_delta_from_readout(
        self,
        pre_patches: jax.Array,
        post_spikes: jax.Array,
        pre_read: jax.Array,
        post_read: jax.Array,
        p: STDPParams,
        *,
        depth: int,
        pairing: str = "nearest",
        compensate: bool = True,
        use_kernel: bool = True,
        interpret: bool = False,
    ) -> jax.Array:
        from repro.kernels.itp_counter.ops import conv_counter_synapse_delta

        self.check_pairing(pairing)
        del compensate
        return conv_counter_synapse_delta(
            pre_patches,
            post_spikes,
            pre_read,
            post_read,
            p,
            depth=depth,
            window=self.window,
            use_kernel=use_kernel,
            interpret=interpret,
        )

    def delta(
        self,
        pre_state: jax.Array,
        post_state: jax.Array,
        pre_spikes: jax.Array,
        post_spikes: jax.Array,
        p: STDPParams,
        *,
        depth: int,
        pairing: str = "nearest",
        compensate: bool = True,
    ) -> jax.Array:
        """Deliberately per-pair: Δt is broadcast to every synapse and the
        window evaluated per pair — the conventional O(n²) datapath the
        intrinsic-timing representation collapses to a register read
        (the measured-cost basis of benchmarks/rule_cost.py)."""
        self.check_pairing(pairing)
        fn = self._window_fn()
        dt_ltp = pre_state[:, None].astype(jnp.float32)  # (n_pre, 1)
        dt_ltd = post_state[None, :].astype(jnp.float32)  # (1, n_post)
        ltp_valid = pre_state[:, None] <= depth - 1
        ltd_valid = post_state[None, :] <= depth - 1
        ltp_mag = fn(dt_ltp, p.a_plus, p.tau_plus, depth) * ltp_valid
        ltd_mag = fn(dt_ltd, p.a_minus, p.tau_minus, depth) * ltd_valid
        ltp_en, ltd_en = pair_gate(pre_spikes[:, None], post_spikes[None, :])
        return ltp_en * ltp_mag - ltd_en * ltd_mag


ITP = register_rule(HistoryRule(name="itp", compensate=None))
ITP_NOCOMP = register_rule(HistoryRule(name="itp_nocomp", compensate=False))
EXACT = register_rule(CounterRule(name="exact", window="exact"))
LINEAR = register_rule(CounterRule(name="linear", window="linear"))
IMSTDP = register_rule(CounterRule(name="imstdp", window="imstdp"))
