"""Distributed ITP-STDP learning engine (docs/architecture.md §8).

Scales the learning engine from the paper's 4×4 prototype to layer-sized
synapse matrices across a device mesh: the weight matrix shards 2-D over
(data, model) ≙ (pre-tiles, post-tiles); each device updates its (pre ×
post) tile from *replicated* spike histories — the update is
embarrassingly parallel because the per-neuron Δw magnitudes are rank-1
(the intrinsic-timing property: no per-synapse state crosses devices).

Per step, the only communication is the postsynaptic current reduction
I_j = Σ_i s_i·w_ij — a psum over the pre-sharded axis (operand = n_post
floats), after which spikes are computed redundantly on every device of a
post-column.  Histories are O(depth · N) bits and stay replicated.

``shard_map``-manual over both axes so the collective schedule is exactly
the one the hardware analogue implies: one reduction per step, nothing
else.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import plasticity
from repro.core.engine import EngineConfig, EngineState, _quantise
from repro.core.lif import LIFState, lif_step
from repro.distributed.sharding import shard_map_compat


def shard_engine_state(state: EngineState, mesh: Mesh,
                       axes: tuple[str, str] = ("data", "model")
                       ) -> EngineState:
    """Place weights 2-D sharded, histories/neurons replicated."""
    w_sh = NamedSharding(mesh, P(*axes))
    rep = NamedSharding(mesh, P())
    return EngineState(
        w=jax.device_put(state.w, w_sh),
        pre_hist=jax.tree_util.tree_map(
            lambda a: jax.device_put(a, rep), state.pre_hist),
        post_hist=jax.tree_util.tree_map(
            lambda a: jax.device_put(a, rep), state.post_hist),
        neurons=jax.tree_util.tree_map(
            lambda a: jax.device_put(a, rep), state.neurons),
    )


def make_sharded_engine_step(cfg: EngineConfig, mesh: Mesh,
                             axes: tuple[str, str] = ("data", "model")):
    """Jitted one-step update with the weight matrix sharded over ``axes``.

    Returns ``step(state, pre_spikes) → (state', post_spikes)``; both
    histories and neuron state replicate, ``state.w`` shards (pre, post).
    """
    pre_ax, post_ax = axes
    rule = cfg.learning_rule()
    # one UpdatePlan owns backend resolution, packed-readout selection and
    # the per-tile fused / event-driven / reference update variants — the
    # same dispatch layer the dense engine and the SNN layers ride
    # (repro.plasticity.apply); this module keeps only what is genuinely
    # about sharding: partition specs, the psum, and the replicated views.
    plan = plasticity.make_plan(cfg)
    # fused and sparse datapaths default to the per-neuron word storage
    # format: the readout crossing shard_map is one uint8 word per neuron
    # ((n,), sharded along axis 0) — the packed register word for the
    # history rules (4·depth× less replicated history traffic than
    # (depth, n) float32; depth > 8 exceeds the word width and keeps the
    # unpacked operands, see EngineConfig.use_packed_history) and the
    # saturating last-spike counter for the counter rules (their only
    # kernel layout).  Row readouts ((rows, n), e.g. generic rank-1
    # rules or the reference backend) shard along their neuron axis.
    words = plan.readout_ndim() == 1

    def local_step(w, pre_spikes, pre_read, post_read, v, pre_ev):
        # w: local (pre_tile, post_tile); spikes and per-neuron readout
        # views shard along their own axes (pre over pre_ax, post over
        # post_ax).  The readout rows are rule-specific — depth bitplane
        # rows for the history rules, one counter row for the Δt rules —
        # but always per-neuron, so the tile update stays local.
        i_local = pre_spikes.astype(jnp.float32) @ w       # (post_tile,)
        i_in = jax.lax.psum(i_local, pre_ax)               # the ONE collective
        neurons, post_spikes = lif_step(LIFState(v=v), i_in, cfg.lif)
        w = plan.tile_update(w, pre_spikes, post_spikes, pre_read,
                             post_read, pre_events=pre_ev, pre_axis=pre_ax)
        if cfg.quantise:
            w = _quantise(w, cfg)
        return w, post_spikes, neurons.v

    # word readouts are (n,) uint8 sharded along axis 0; row readouts are
    # (rows, n) with the neuron axis second
    pre_read_spec = P(pre_ax) if words else P(None, pre_ax)
    post_read_spec = P(post_ax) if words else P(None, post_ax)
    sharded = shard_map_compat(
        local_step, mesh=mesh,
        in_specs=(P(pre_ax, post_ax),      # w tile
                  P(pre_ax),               # pre spikes (sharded like rows)
                  pre_read_spec,           # pre history readout
                  post_read_spec,          # post history readout
                  P(post_ax),              # membrane (sharded like cols)
                  P()),                    # global pre events (replicated)
        out_specs=(P(pre_ax, post_ax), P(post_ax), P(post_ax)))

    @jax.jit
    def step(state: EngineState, pre_spikes: jax.Array):
        pre_read = plan.state_readout(state.pre_hist)
        post_read = plan.state_readout(state.post_hist)
        # sparse: the global presynaptic event list is extracted ONCE
        # outside shard_map (pre spikes are replicated inputs) and
        # crosses as a replicated static-shape (cap,) index vector; each
        # tile translates the global indices into its own row range
        # (plan.tile_update).  Postsynaptic events are extracted locally
        # per tile — post spikes are computed redundantly on every device
        # of a post-column anyway, so the local extraction adds no
        # communication.  Dense backends cross a zero-length vector.
        pre_ev = plan.pre_events_crossing(pre_spikes)
        w, post_spikes, v = sharded(state.w,
                                    pre_spikes.astype(jnp.float32),
                                    pre_read,
                                    post_read,
                                    state.neurons.v,
                                    pre_ev)
        post_bool = post_spikes.astype(jnp.bool_)
        new_state = EngineState(
            w=w,
            pre_hist=rule.step(state.pre_hist, pre_spikes, depth=cfg.depth),
            post_hist=rule.step(state.post_hist, post_bool, depth=cfg.depth),
            neurons=type(state.neurons)(v=v),
        )
        return new_state, post_bool

    return step
