"""First-class learning-rule abstraction for the update path.

The paper's headline results are *comparative*: ITP-STDP against the
original counter-based exact STDP and simpler approximations on the same
networks — one register-file datapath, a family of rules (Tables III–V).
This module is the platform contract that makes the family real.

The **slim protocol** a rule actually has to write is small — it declares
its timing state, its readout views, and its window/delta semantics:

  * ``init_state``             — the per-population timing state
                                 (bitplane spike histories, last-spike
                                 counters, eligibility traces, …);
  * ``step``                   — recording the current step's spikes
                                 (the hardware 'shift-in' / counter
                                 reset / trace decay);
  * ``readout``                — a dense ``(rows, n)`` view of that
                                 state (the arrays-only form shard_map
                                 and the oracles consume);
  * ``magnitudes_from_readout``— the per-neuron Δw magnitude read from
                                 such a view (the rank-1 window
                                 semantics);
  * ``last_spikes``            — the k=0 spike indicator (lateral
                                 inhibition).

Everything *backend*-shaped on top of that — which kernel runs, packed
vs unpacked operands, dense vs conv vs sharded shape plumbing — lives in
exactly one place, ``repro.plasticity.apply``: consumers build an
``UpdatePlan`` from their config and never branch on backends or call a
hook themselves (machine-checked by lint rule R8).  The plan talks to
rules through the hook seam defined here (``kernel_readout`` /
``*_from_readout``), and :class:`Rank1Rule` implements that entire seam
generically for any rule whose update is a pair-gated rank-1 outer
product of per-neuron magnitudes: the generic adapters feed the
magnitude vectors through the existing ``itp_stdp`` / ``itp_stdp_conv``
/ ``itp_sparse`` datapaths as a single depth-1 plane with unit po2
weights, so a new rule inherits the fused, sparse, conv, and sharded
machinery from its five slim methods with zero kernel code.

The built-in families predate :class:`Rank1Rule` and keep their
hand-tuned hooks: the intrinsic-timing rules route to the ``itp_stdp``
/ ``itp_stdp_conv`` kernels on the packed register words, the
explicit-Δt counter family to the ``itp_counter`` kernels on its uint8
counter word.  ``has_kernel``/``has_sparse`` declare which backends a
rule supports; a rule without them is rejected on the ``fused*`` /
``sparse`` backends at config-construction time with the full option
list (:func:`resolve_rule_backend`), so the rule × backend matrix
(ROADMAP) is explicit rather than discovered at trace time.
"""

from __future__ import annotations

import abc
from typing import Any

import jax
import jax.numpy as jnp

from repro.core.stdp import STDPParams, pair_gate
from repro.kernels.dispatch import BACKENDS, resolve_backend


class LearningRule(abc.ABC):
    """Protocol every STDP-variant learning rule implements.

    ``name`` is the registry key; ``has_kernel`` marks rules whose state
    layout the fused Pallas kernels consume; ``has_sparse`` marks rules
    that own the event-driven datapath (``backend="sparse"``);
    ``compensate`` is ``None`` when the rule defers to the config's
    compensation flag (the default 'itp' behaviour) or a hard
    ``True``/``False`` override.
    """

    name: str = ""
    has_kernel: bool = False
    has_sparse: bool = False
    compensate: bool | None = None

    # -- state ---------------------------------------------------------
    @abc.abstractmethod
    def init_state(self, n: int, depth: int) -> Any:
        """Fresh timing state for a population of ``n`` neurons."""

    @abc.abstractmethod
    def step(self, state: Any, spikes: jax.Array, *, depth: int) -> Any:
        """Record the current step's spikes (shift-in / counter reset)."""

    # -- readout -------------------------------------------------------
    @abc.abstractmethod
    def readout(self, state: Any) -> jax.Array:
        """Dense ``(rows, n)`` float view of the state for shard_map.

        Row count is rule-specific (``depth`` bitplane rows for history
        rules, one counter row for Δt rules); shards along axis 1.
        """

    def readout_packed(self, state: Any) -> jax.Array:
        """Packed ``(n,)`` uint8 view of the state — one word per neuron.

        For the history rules this is the register word of the paper's
        8-bit register file (``repro.core.history.pack_words``, MSB =
        most recent, depth ≤ 8); for the counter rules it is the
        saturating last-spike counter itself.  Either way it is the
        storage format the rule's fused Pallas kernel consumes and shards
        along axis 0.  Only kernel-backed rules (``has_kernel``)
        implement it — the fused datapaths are unreachable for the others
        (:func:`resolve_rule_backend` rejects them at config time).
        """
        raise NotImplementedError(f"rule {self.name!r} has no packed (kernel) state layout")

    # -- session serialization (the serving "plasticity cache") --------
    # A rule's full timing state round-trips through a small tuple of
    # per-neuron uint8 word planes — the resident per-user state of the
    # serving layer (repro.serve) and the byte count the paper's 1-byte-
    # per-synapse-state claim prices.  ``state_from_words`` must invert
    # ``serve_words`` up to representations with identical continued
    # trajectories (the ring-buffer head is canonicalised away: every
    # readout is rotation-invariant, pinned by tests/test_serve.py).
    # Like the kernel hooks these are called only through the
    # ``UpdatePlan`` session methods (lint rule R8).

    def words_per_neuron(self) -> int:
        """Resident uint8 words per neuron of the serialized state."""
        raise NotImplementedError(f"rule {self.name!r} has no word serialization")

    def serve_words(self, state: Any) -> tuple[jax.Array, ...]:
        """Canonical ``words_per_neuron()``-tuple of ``(n,)`` uint8 words."""
        raise NotImplementedError(f"rule {self.name!r} has no word serialization")

    def state_from_words(self, words: tuple[jax.Array, ...], *, depth: int) -> Any:
        """Rebuild a timing state from :meth:`serve_words` output.

        The rebuilt state's continued trajectory (weights, spikes, and
        re-serialized words) must be bit-identical to the original's.
        """
        raise NotImplementedError(f"rule {self.name!r} has no word serialization")

    # -- fused (kernel) datapath ---------------------------------------
    # Rules with ``has_kernel=True`` own their fused Pallas datapath via
    # these hooks; the engine, sharded engine, and SNN layers dispatch
    # through them instead of importing a kernel package directly.

    def kernel_readout(self, state: Any, *, packed: bool) -> jax.Array:
        """The state view the rule's fused kernel consumes.

        ``packed=True`` selects the per-neuron word layout (``(n,)``
        uint8, axis-0 sharded); ``packed=False`` the dense row layout
        (``(rows, n)`` float32, axis-1 sharded).  Rules whose kernel has
        a single natural operand layout (the counter rules: one uint8
        word per neuron either way) may ignore ``packed``.
        """
        raise NotImplementedError(f"rule {self.name!r} has no fused kernel")

    def kernel_readout_axes(self, *, packed: bool) -> int:
        """ndim of :meth:`kernel_readout`'s result (1 = words, 2 = rows).

        Lets ``shard_map`` callers build partition specs before any state
        exists: a 1-D word readout shards along axis 0, a 2-D row readout
        along axis 1.
        """
        raise NotImplementedError(f"rule {self.name!r} has no fused kernel")

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
        """Fused clipped weight RMW from :meth:`kernel_readout` views."""
        raise NotImplementedError(f"rule {self.name!r} has no fused kernel")

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
        """Raw fused ``(n_pre, n_post)`` Δw (no eta/clip), summed over a batch.

        The SNN fc layers call this once per step with the whole batch:
        spikes ``(B, n)`` and :meth:`kernel_readout` views with the batch
        before the neuron axis — words ``(B, n)``, rows ``(rows, B, n)``
        (the conv hooks' patch layout with one position per sample)."""
        raise NotImplementedError(f"rule {self.name!r} has no fused kernel")

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
        """Raw ``(K, C)`` conv-layer delta from im2col'd readout views.

        ``pre_read``/``post_read`` are :meth:`kernel_readout` views
        gathered into the im2col patch layout by the caller; unlike the
        dense hooks this one also serves ``use_kernel=False`` (the
        pure-jnp oracle), so conv layers have exactly one dispatch path
        per rule.
        """
        raise NotImplementedError(f"rule {self.name!r} has no fused kernel")

    # -- event-driven (sparse) datapath --------------------------------
    # Rules with ``has_sparse=True`` own the event-driven datapath of
    # ``repro.kernels.itp_sparse``: static-shape event lists gate
    # gather/scatter updates of only the touched weight slices.  The
    # readout views are the same ones :meth:`kernel_readout` produces
    # (packed uint8 words or dense rows) so the sparse backend shares
    # the fused backends' storage format and sharding contract.

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
        """Event-driven clipped weight RMW from readout views.

        ``pre_events``/``post_events`` let shard_map callers ship
        precomputed (tile-translated) event lists; ``None`` extracts
        them from the current-step spikes under ``max_events``.
        """
        raise NotImplementedError(f"rule {self.name!r} has no event-driven datapath")

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
        """Raw event-driven ``(n_pre, n_post)`` Δw (no eta/clip) — the
        batched SNN fc layers vmap this over samples and accumulate."""
        raise NotImplementedError(f"rule {self.name!r} has no event-driven datapath")

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
        """Raw ``(K, C)`` conv delta, im2col on gathered active rows only.

        Same operand layout as :meth:`conv_delta_from_readout` with
        ``use_kernel=False`` (dense bitplane readouts in the im2col
        patch layout); the active-row event list caps at ``max_events``.
        """
        raise NotImplementedError(f"rule {self.name!r} has no event-driven datapath")

    @abc.abstractmethod
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
        """Per-neuron Δw magnitude ``(n,)`` from a :meth:`readout` view."""

    def magnitudes(
        self,
        state: Any,
        amplitude: float,
        tau: float,
        *,
        depth: int,
        pairing: str = "nearest",
        compensate: bool = True,
    ) -> jax.Array:
        """Per-neuron Δw magnitude ``(n,)`` read from the timing state."""
        arr = self.readout(state)
        return self.magnitudes_from_readout(
            arr, amplitude, tau, depth=depth, pairing=pairing, compensate=compensate
        )

    def last_spikes(self, state: Any) -> jax.Array:
        """``(n,)`` f32 indicator of a spike on the previous step.

        Used by the lateral-inhibition path; rules expose the k=0 view of
        their timing state (1 iff the most recent recorded event was a
        spike).
        """
        raise NotImplementedError

    def check_pairing(self, pairing: str) -> None:
        """Raise ``ValueError`` if the rule cannot express ``pairing``."""
        if pairing not in ("nearest", "all"):
            raise ValueError(f"pairing must be 'nearest' or 'all', got {pairing!r}")

    # -- dense update --------------------------------------------------
    def delta(
        self,
        pre_state: Any,
        post_state: Any,
        pre_spikes: jax.Array,
        post_spikes: jax.Array,
        p: STDPParams,
        *,
        depth: int,
        pairing: str = "nearest",
        compensate: bool = True,
    ) -> jax.Array:
        """Raw pair-gated ``(n_pre, n_post)`` Δw (no eta, clip, quantise).

        Default: rank-1 gated outer product of the per-neuron magnitudes
        — the intrinsic-timing datapath.  Δt-based rules override this
        with their deliberately per-pair formulation so the measured cost
        asymmetry (benchmarks/rule_cost.py) reflects the conventional
        datapath the paper optimises away.
        """
        ltp = self.magnitudes(
            pre_state, p.a_plus, p.tau_plus, depth=depth, pairing=pairing, compensate=compensate
        )
        ltd = self.magnitudes(
            post_state, p.a_minus, p.tau_minus, depth=depth, pairing=pairing, compensate=compensate
        )
        ltp_en, ltd_en = pair_gate(pre_spikes[:, None], post_spikes[None, :])
        return ltp_en * ltp[:, None] - ltd_en * ltd[None, :]


# ---------------------------------------------------------------------------
# Generic rank-1 backend adapters
# ---------------------------------------------------------------------------

# Unit STDP params for the magnitude-plane adapters below: with a single
# depth-1 plane the kernels' po2 weighting is exp2(0) = 1.0 for any tau,
# so `po2 @ plane` returns the plane itself and the amplitudes must not
# be applied twice.
_UNIT_PARAMS = STDPParams(a_plus=1.0, a_minus=1.0)


class Rank1Rule(LearningRule):
    """Slim-protocol base: every backend from five rule-owned methods.

    For any rule whose dense update is the pair-gated rank-1 form

        ``dw = gate_ltp * ltp[:, None] - gate_ltd * ltd[None, :]``

    with per-neuron magnitudes ``ltp``/``ltd`` read from the state, the
    whole backend hook seam is derivable — so this base implements it
    once, generically, and a subclass only writes the slim protocol
    (``init_state`` / ``step`` / ``readout`` /
    ``magnitudes_from_readout`` / ``last_spikes``).

    The trick that makes the adapters exact with **zero new kernel
    code**: the existing intrinsic-timing datapaths all compute their
    per-neuron magnitudes as ``po2_weights(depth, tau) @ bitplanes``
    before the shared XOR-gate/outer-product/scatter machinery.  Feeding
    them the rule's already-computed magnitude vector as a single
    depth-1 "bitplane" with unit amplitudes (``po2_weights(1, tau) =
    [exp2(0)] = [1.0]`` for any tau, compensated or not) makes that dot
    product the identity: ``1.0 * m == m`` exactly in float32.  Pairing
    is forced to ``"all"`` inside the adapters because the
    nearest-spike cumsum mask assumes binary planes — the rule's own
    ``magnitudes_from_readout`` already owns whatever pairing semantics
    it supports.

    Subclasses default to the full backend column (``has_kernel`` and
    ``has_sparse`` both True); opt out by overriding the flags and the
    config-construction validator rejects the missing cells with the
    usual option listing.
    """

    has_kernel: bool = True
    has_sparse: bool = True

    # -- readout views --------------------------------------------------

    def kernel_readout(self, state: Any, *, packed: bool) -> jax.Array:
        """Generic rules have one layout — the dense readout rows.

        ``packed`` is a storage-format optimisation of the built-in
        families' register words; a generic rule's rows are its storage
        format, so the flag is accepted (the plan passes it uniformly)
        and ignored.
        """
        del packed
        return self.readout(state)

    def kernel_readout_axes(self, *, packed: bool) -> int:
        del packed
        return 2

    def readout_packed(self, state: Any) -> jax.Array:
        raise NotImplementedError(
            f"rule {self.name!r} has no packed word layout: generic "
            f"rank-1 rules ship their dense readout rows to every backend"
        )

    def _readout_magnitudes(
        self,
        arr: jax.Array,
        amplitude: float,
        tau: float,
        *,
        depth: int,
        pairing: str,
        compensate: bool,
    ) -> jax.Array:
        """``magnitudes_from_readout`` over views with trailing dims.

        The conv adapters receive ``(rows, M, K)`` patch views; flatten
        the trailing dims to the ``(rows, n)`` contract, read, reshape
        back.
        """
        rows = arr.shape[0]
        flat = arr.reshape(rows, -1)
        m = self.magnitudes_from_readout(
            flat, amplitude, tau, depth=depth, pairing=pairing, compensate=compensate
        )
        return m.reshape(arr.shape[1:])

    def _magnitude_pair(
        self, pre_read, post_read, p, *, depth, pairing, compensate
    ) -> tuple[jax.Array, jax.Array]:
        ltp = self._readout_magnitudes(
            pre_read, p.a_plus, p.tau_plus, depth=depth, pairing=pairing, compensate=compensate
        )
        ltd = self._readout_magnitudes(
            post_read, p.a_minus, p.tau_minus, depth=depth, pairing=pairing, compensate=compensate
        )
        return ltp, ltd

    # -- fused (kernel) datapath ---------------------------------------

    def fused_update_from_readout(
        self,
        w,
        pre_spike,
        post_spike,
        pre_read,
        post_read,
        p,
        *,
        depth,
        pairing="nearest",
        compensate=True,
        eta=1.0,
        w_min=0.0,
        w_max=1.0,
        interpret=False,
    ):
        from repro.kernels.itp_stdp.ops import weight_update_depth_major

        ltp, ltd = self._magnitude_pair(
            pre_read, post_read, p, depth=depth, pairing=pairing, compensate=compensate
        )
        return weight_update_depth_major(
            w,
            pre_spike,
            post_spike,
            ltp[None, :],
            ltd[None, :],
            _UNIT_PARAMS,
            pairing="all",
            compensate=False,
            eta=eta,
            w_min=w_min,
            w_max=w_max,
            interpret=interpret,
        )

    def fused_delta_from_readout(
        self,
        pre_spike,
        post_spike,
        pre_read,
        post_read,
        p,
        *,
        depth,
        pairing="nearest",
        compensate=True,
        interpret=False,
    ):
        from repro.kernels.itp_stdp.ops import synapse_delta

        ltp, ltd = self._magnitude_pair(
            pre_read, post_read, p, depth=depth, pairing=pairing, compensate=compensate
        )
        return synapse_delta(
            pre_spike,
            post_spike,
            ltp[None, :],
            ltd[None, :],
            _UNIT_PARAMS,
            pairing="all",
            compensate=False,
            interpret=interpret,
        )

    def conv_delta_from_readout(
        self,
        pre_patches,
        post_spikes,
        pre_read,
        post_read,
        p,
        *,
        depth,
        pairing="nearest",
        compensate=True,
        use_kernel=True,
        interpret=False,
    ):
        from repro.kernels.itp_stdp_conv.ops import conv_synapse_delta

        ltp, ltd = self._magnitude_pair(
            pre_read, post_read, p, depth=depth, pairing=pairing, compensate=compensate
        )
        return conv_synapse_delta(
            pre_patches,
            post_spikes,
            ltp[None],
            ltd[None],
            _UNIT_PARAMS,
            pairing="all",
            compensate=False,
            use_kernel=use_kernel,
            interpret=interpret,
        )

    # -- event-driven (sparse) datapath --------------------------------

    def sparse_update_from_readout(
        self,
        w,
        pre_spike,
        post_spike,
        pre_read,
        post_read,
        p,
        *,
        depth,
        pairing="nearest",
        compensate=True,
        eta=1.0,
        w_min=0.0,
        w_max=1.0,
        max_events=None,
        pre_events=None,
        post_events=None,
    ):
        from repro.kernels.itp_sparse.ops import sparse_weight_update

        ltp, ltd = self._magnitude_pair(
            pre_read, post_read, p, depth=depth, pairing=pairing, compensate=compensate
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
        pre_spike,
        post_spike,
        pre_read,
        post_read,
        p,
        *,
        depth,
        pairing="nearest",
        compensate=True,
        max_events=None,
    ):
        from repro.kernels.itp_sparse.ops import sparse_synapse_delta

        ltp, ltd = self._magnitude_pair(
            pre_read, post_read, p, depth=depth, pairing=pairing, compensate=compensate
        )
        return sparse_synapse_delta(pre_spike, post_spike, ltp, ltd, max_events=max_events)

    def sparse_conv_delta_from_readout(
        self,
        pre_patches,
        post_spikes,
        pre_read,
        post_read,
        p,
        *,
        depth,
        pairing="nearest",
        compensate=True,
        max_events=None,
    ):
        from repro.kernels.itp_sparse.ops import sparse_conv_delta

        ltp, ltd = self._magnitude_pair(
            pre_read, post_read, p, depth=depth, pairing=pairing, compensate=compensate
        )
        po2_one = jnp.ones((1,), jnp.float32)
        return sparse_conv_delta(
            pre_patches,
            post_spikes,
            ltp[None],
            ltd[None],
            po2_one,
            po2_one,
            nearest=False,
            max_events=max_events,
        )


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

RULES: dict[str, LearningRule] = {}


def register_rule(rule: LearningRule) -> LearningRule:
    """Add ``rule`` to the registry (keyed by ``rule.name``)."""
    if not rule.name:
        raise ValueError("learning rule must carry a non-empty name")
    RULES[rule.name] = rule
    return rule


def rule_names() -> tuple[str, ...]:
    return tuple(sorted(RULES))


def get_rule(name: str) -> LearningRule:
    """Look up a registered rule; unknown names list the valid options."""
    try:
        return RULES[name]
    except KeyError as e:
        raise ValueError(f"unknown learning rule {name!r}; have {rule_names()}") from e


def kernel_rule_names() -> tuple[str, ...]:
    return tuple(sorted(n for n, r in RULES.items() if r.has_kernel))


def sparse_rule_names() -> tuple[str, ...]:
    return tuple(sorted(n for n, r in RULES.items() if r.has_sparse))


def validate_update_config(
    *,
    rule: str,
    backend: str,
    pairing: str,
    max_events: int | None,
) -> LearningRule:
    """Single cross-field validator shared by ``EngineConfig`` and ``SNNConfig``.

    Every constraint the two configs share lives here exactly once, so the
    error messages (and their valid-option listings) cannot drift between
    them: unknown rule/backend names list the registry options, kernel-less
    rules reject the ``fused*`` backends, rules without event hooks reject
    ``sparse``, counter rules reject ``pairing="all"``, and ``max_events``
    must be a positive cap or ``None``.  Returns the resolved rule so
    callers avoid a second registry lookup.
    """
    resolved = get_rule(rule)
    resolve_rule_backend(resolved, backend)
    resolved.check_pairing(pairing)
    if max_events is not None and max_events < 1:
        raise ValueError(
            f"max_events must be a positive event-list cap or None "
            f"(uncapped), got {max_events}"
        )
    return resolved


def resolve_rule_backend(rule: str | LearningRule, backend: str) -> tuple[bool, bool]:
    """Validate a (rule, backend) cell and map it to (use_kernel, interpret).

    Unknown rule or backend names raise ``ValueError`` listing the valid
    options; a kernel-less rule on a ``fused*`` backend — or a rule
    without event hooks on the ``sparse`` backend — is rejected with the
    actionable alternatives (the ROADMAP rule × backend matrix), never
    at trace time.
    """
    if isinstance(rule, str):
        rule = get_rule(rule)
    use_kernel, interpret = resolve_backend(backend)
    if use_kernel and not rule.has_kernel:
        raise ValueError(
            f"rule {rule.name!r} has no fused kernel: backend {backend!r} is "
            f"only available for the kernel-backed rules "
            f"{kernel_rule_names()}; use backend='reference' for "
            f"{rule.name!r} (valid backends: {BACKENDS})"
        )
    if backend == "sparse" and not rule.has_sparse:
        raise ValueError(
            f"rule {rule.name!r} has no event-driven datapath: backend "
            f"'sparse' is only available for the event-hook rules "
            f"{sparse_rule_names()}; use backend='reference' for "
            f"{rule.name!r} (valid backends: {BACKENDS})"
        )
    return use_kernel, interpret
