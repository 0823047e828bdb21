"""Compile the fused datapath for a described TPU v5e, without a chip.

The TPU compiler is installed with jaxlib and compiles for a topology that
is described rather than attached, so these tests catch what the Pallas
interpreter cannot: ops Mosaic has no lowering for, layouts it refuses, and
a whole program that loses its kernel.  Nothing runs and nothing is timed.

The topology is described in a module fixture, never at import: only one
process may load the TPU library at a time, and every xdist worker imports
this module, so describing it at import would make the workers collect
different tests.  The persistent compilation cache is off around the
compiles, since a cache entry compiled for a described chip cannot be read
back without one.
"""
from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro import tracing
from repro.core.engine import EngineConfig, engine_step, init_engine
from repro.core.engine_sharded import make_sharded_engine_step
from repro.kernels.itp_counter.kernel import counter_stdp_update
from repro.kernels.itp_stdp.kernel import itp_stdp_update_packed
from repro.kernels.itp_stdp_conv.kernel import itp_stdp_conv_delta_packed
from repro.models import snn

DEPTH = 7
# 2layer-snn fc layer, 784 -> 100, padded to lanes as the ops wrappers do
FC_PRE, FC_POST = 896, 128
# 6layer-dcsnn conv1 at batch 16: 24 x 24 output positions per sample,
# 5 x 5 x 1 patches (25 -> 128 lanes), 12 channels (-> 128 lanes)
CONV_M, CONV_K, CONV_C = 16 * 24 * 24, 128, 128


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _specs(sharding, tree):
    return jax.tree_util.tree_map(lambda a: _spec(sharding, a.shape, a.dtype), tree)


def _compile_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("nearest", [True, False], ids=["nearest", "all_to_all"])
def test_dense_kernel_compiles_at_2layer_width(one_chip, nearest):
    def fn(w, ps, qs, pw, qw, lt, ld):
        return itp_stdp_update_packed(
            w, ps, qs, pw, qw, lt, ld, depth=DEPTH, nearest=nearest, tile_pre=128, tile_post=128
        )

    s = one_chip
    text = _compile_text(
        fn,
        _spec(s, (FC_PRE, FC_POST)),
        _spec(s, (FC_PRE,)),
        _spec(s, (FC_POST,)),
        _spec(s, (FC_PRE,), jnp.uint8),
        _spec(s, (FC_POST,), jnp.uint8),
        _spec(s, (DEPTH,)),
        _spec(s, (DEPTH,)),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("nearest", [True, False], ids=["nearest", "all_to_all"])
def test_batched_dense_kernel_compiles_at_b256(one_chip, nearest):
    """The fc update of a B = 256 training step: the batch rows are the
    contracting axis of the kernel's two MXU dots, 128 rows per grid step."""
    batch = 256

    def fn(w, ps, qs, pw, qw, lt, ld):
        return itp_stdp_update_packed(
            w, ps, qs, pw, qw, lt, ld, depth=DEPTH, nearest=nearest, tile_pre=128,
            tile_post=128, tile_batch=128,
        )

    s = one_chip
    text = _compile_text(
        fn,
        _spec(s, (FC_PRE, FC_POST)),
        _spec(s, (batch, FC_PRE)),
        _spec(s, (batch, FC_POST)),
        _spec(s, (batch, FC_PRE), jnp.uint8),
        _spec(s, (batch, FC_POST), jnp.uint8),
        _spec(s, (DEPTH,)),
        _spec(s, (DEPTH,)),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("nearest", [True, False], ids=["nearest", "all_to_all"])
def test_conv_kernel_compiles_at_dcsnn_conv1(one_chip, nearest):
    def fn(pre, post, pw, qw, lt, ld):
        return itp_stdp_conv_delta_packed(pre, post, pw, qw, lt, ld, depth=DEPTH, nearest=nearest)

    s = one_chip
    text = _compile_text(
        fn,
        _spec(s, (CONV_M, CONV_K)),
        _spec(s, (CONV_M, CONV_C)),
        _spec(s, (CONV_M, CONV_K), jnp.uint8),
        _spec(s, (CONV_M, CONV_C), jnp.uint8),
        _spec(s, (DEPTH,)),
        _spec(s, (DEPTH,)),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("window", ["exact", "linear", "imstdp"])
def test_counter_kernel_compiles_at_2layer_width(one_chip, window):
    def fn(w, ps, qs, pw, qw):
        return counter_stdp_update(
            w,
            ps,
            qs,
            pw,
            qw,
            depth=DEPTH,
            window=window,
            a_plus=0.01,
            a_minus=0.012,
            tau_plus=3.0,
            tau_minus=4.0,
            tile_pre=128,
            tile_post=128,
        )

    s = one_chip
    text = _compile_text(
        fn,
        _spec(s, (FC_PRE, FC_POST)),
        _spec(s, (FC_PRE,)),
        _spec(s, (FC_POST,)),
        _spec(s, (FC_PRE,), jnp.uint8),
        _spec(s, (FC_POST,), jnp.uint8),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("net", ["2layer-snn", "6layer-dcsnn", "5layer-csnn"])
def test_run_snn_compiles_with_kernel(one_chip, net):
    """The whole training scan at batch 16, 30 steps, on ``fused``."""
    cfg = snn.PAPER_NETWORKS[net]("itp", backend="fused")
    batch, t_steps = 16, 30
    # build the state on the host and keep only the shapes
    state = _specs(one_chip, snn.init_snn(jax.random.PRNGKey(0), cfg, batch))
    n_in = 1
    for d in cfg.input_shape:
        n_in *= d
    raster = _spec(one_chip, (t_steps, batch, n_in), jnp.uint8)
    text = snn.run_snn.lower(state, raster, cfg, train=True).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("train", [True, False], ids=["train", "eval"])
@pytest.mark.parametrize("net", ["2layer-snn", "6layer-dcsnn"])
def test_run_snn_scopes_survive_the_tpu_compiler(one_chip, net, train):
    """The device scopes in the chip's optimised text, and every kernel
    custom call named after its entry point, under ``stdp.update``."""
    cfg = snn.PAPER_NETWORKS[net]("itp", backend="fused")
    batch, t_steps = 16, 30
    state = _specs(one_chip, snn.init_snn(jax.random.PRNGKey(0), cfg, batch))
    raster = _spec(one_chip, (t_steps, batch, 784), jnp.uint8)
    text = snn.run_snn.lower(state, raster, cfg, train=train).compile().as_text()
    scopes = {tracing.FORWARD, tracing.TIMING} | ({tracing.UPDATE} if train else set())
    for scope in tracing.DEVICE_SCOPES:
        assert (f"/{scope}/" in text) == (scope in scopes), scope
    calls = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    assert bool(calls) == train
    entry = "itp_stdp_update_packed" if net == "2layer-snn" else "itp_stdp_conv_delta_packed"
    assert any(f"%{entry}" in line for line in calls) == train
    for line in calls:
        assert f"/{tracing.UPDATE}/" in line
        assert re.search(r"%(itp_stdp_update_packed|itp_stdp_conv_delta_packed)[.\d]* = ", line)


@pytest.mark.parametrize("rule", ["itp", "itp_nocomp", "exact", "linear", "imstdp"])
def test_engine_step_compiles_with_kernel(one_chip, rule):
    cfg = EngineConfig(n_pre=784, n_post=128, rule=rule, backend="fused")
    state = _specs(one_chip, jax.eval_shape(lambda k: init_engine(k, cfg), jax.random.PRNGKey(0)))
    spikes = _spec(one_chip, (cfg.n_pre,), jnp.bool_)
    text = _compile_text(lambda s, x: engine_step(s, x, cfg), state, spikes)
    assert "tpu_custom_call" in text


def test_sharded_engine_compiles_on_four_chips(one_chip, topo):
    """The 2-D weight-sharded engine at 4096 x 4096 over a 2 x 2 mesh of
    described chips: one kernel per tile and the one current all-reduce."""
    mesh = Mesh(
        np.array(topo.devices[:4]).reshape(2, 2), ("data", "model"), axis_types=(AxisType.Auto,) * 2
    )
    cfg = EngineConfig(n_pre=4096, n_post=4096, rule="itp", backend="fused")
    shapes = jax.eval_shape(lambda k: init_engine(k, cfg), jax.random.PRNGKey(0))
    state = _specs(NamedSharding(mesh, P()), shapes)
    state = state._replace(w=_spec(NamedSharding(mesh, P("data", "model")), state.w.shape))
    spikes = _spec(NamedSharding(mesh, P()), (cfg.n_pre,), jnp.bool_)
    step = make_sharded_engine_step(cfg, mesh)
    text = step.lower(state, spikes).compile().as_text()
    assert "tpu_custom_call" in text
    assert "all-reduce" in text
