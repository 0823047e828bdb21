"""Layer 2 — jaxpr contract audit of the rule × backend × layer-kind matrix.

Traces every *valid* matrix cell abstractly (``jax.eval_shape`` for
state construction, ``jax.make_jaxpr`` for the step — nothing executes,
Pallas kernels abstract-eval without compiling) and checks the dataflow
contracts the paper's hardware makes statically:

* the cell traces clean on this toolchain,
* no float64 aval anywhere in the graph (x64 creep),
* no weak-typed top-level outputs (recompilation hazard: a weak output
  fed back as input retraces with a different aval),
* the timing state round-trips with identical dtypes (the uint8 history
  planes / int32 counters never silently promote), and
* cells whose datapath reads packed registers (history rules always;
  counter rules on kernel/sparse backends) actually carry uint8 operands
  in the graph.

Each cell also records a primitive-count table — a host-independent cost
fingerprint of the traced graph.  ``benchmarks/static_audit.py`` writes
it to the tracked ``BENCH_static.json``, which CI diffs against to catch
silent graph bloat the wall-clock benchmarks can't resolve.
"""
from __future__ import annotations

import collections
from typing import Any, Iterable

import jax
import jax.extend.core as jex_core
import jax.numpy as jnp

from repro import plasticity
from repro.core.engine import EngineConfig, engine_step, init_engine
from repro.kernels.dispatch import BACKENDS
from repro.models.snn import SNNConfig, SNNLayerSpec, init_snn, snn_step

KINDS = ("engine", "fc", "conv2d", "conv1d")

# tiny but layout-representative shapes: big enough to exercise the
# packing (n > 8 → multi-word registers) and conv patch extraction,
# small enough that 60+ abstract traces stay CI-cheap
_SPARSE_EVENTS = 4
_SNN_SHAPES = {
    "fc": ((16,), SNNLayerSpec("fc", out_features=8)),
    "conv2d": ((8, 8, 1), SNNLayerSpec("conv2d", out_features=4, kernel=3)),
    "conv1d": ((16, 2), SNNLayerSpec("conv1d", out_features=4, kernel=3, stride=2)),
}


def valid_cells(kinds: Iterable[str] = KINDS) -> list[tuple[str, str, str]]:
    """All (rule, backend, kind) combinations the shared validator accepts."""
    out = []
    for kind in kinds:
        for rule in plasticity.rule_names():
            for backend in BACKENDS:
                max_events = _SPARSE_EVENTS if backend == "sparse" else None
                try:
                    plasticity.validate_update_config(
                        rule=rule,
                        backend=backend,
                        pairing="nearest",
                        max_events=max_events,
                    )
                except ValueError:
                    continue
                out.append((rule, backend, kind))
    return out


def _abstract(tree):
    return jax.tree_util.tree_map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)


def _cell_program(rule: str, backend: str, kind: str):
    """→ (state shapes, input ShapeDtypeStruct, traced step fn).

    The init functions are eager-only (they size buffers with Python
    ints), so the state is built concretely at the audit's tiny shapes
    and abstracted to ShapeDtypeStructs; only the *step* is traced.
    """
    key = jax.random.PRNGKey(0)
    max_events = _SPARSE_EVENTS if backend == "sparse" else None
    if kind == "engine":
        cfg = EngineConfig(n_pre=16, n_post=8, rule=rule, backend=backend, max_events=max_events)
        state = _abstract(init_engine(key, cfg))
        x = jax.ShapeDtypeStruct((cfg.n_pre,), jnp.bool_)
        return state, x, lambda s, sp: engine_step(s, sp, cfg)
    input_shape, spec = _SNN_SHAPES[kind]
    cfg = SNNConfig(
        name=f"audit-{kind}",
        input_shape=input_shape,
        layers=(spec,),
        rule=rule,
        backend=backend,
        max_events=max_events,
    )
    state = _abstract(init_snn(key, cfg, 1))
    x = jax.ShapeDtypeStruct((1, *input_shape), jnp.bool_)
    return state, x, lambda s, sp: snn_step(s, sp, cfg, train=True)


def _sub_jaxprs(value: Any):
    """Recursively yield jaxprs hiding in an eqn param value (pjit/cond/
    scan/pallas_call all stash them under different shapes)."""
    if isinstance(value, jex_core.ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, jex_core.Jaxpr):
        yield value
    elif isinstance(value, (list, tuple)):
        for v in value:
            yield from _sub_jaxprs(v)
    elif isinstance(value, dict):
        for v in value.values():
            yield from _sub_jaxprs(v)


def _walk(jaxpr) -> Iterable:
    """All jaxprs reachable from ``jaxpr`` (itself included)."""
    seen = []
    stack = [jaxpr]
    while stack:
        j = stack.pop()
        if any(j is s for s in seen):
            continue
        seen.append(j)
        yield j
        for eqn in j.eqns:
            for v in eqn.params.values():
                stack.extend(_sub_jaxprs(v))


def _avals(jaxpr) -> Iterable:
    for j in _walk(jaxpr):
        for var in list(j.invars) + list(j.constvars):
            yield var.aval
        for eqn in j.eqns:
            for var in list(eqn.invars) + list(eqn.outvars):
                aval = getattr(var, "aval", None)
                if aval is not None:
                    yield aval


def _state_dtypes(tree) -> list[str]:
    return [str(leaf.dtype) for leaf in jax.tree_util.tree_leaves(tree)]


def audit_cell(rule: str, backend: str, kind: str) -> dict:
    """Trace one matrix cell and check its contracts; never raises."""
    cell: dict[str, Any] = {"rule": rule, "backend": backend, "kind": kind, "violations": []}
    try:
        state, x, fn = _cell_program(rule, backend, kind)
        closed = jax.make_jaxpr(fn)(state, x)
        out_shapes = jax.eval_shape(fn, state, x)
    except Exception as e:  # noqa: BLE001 — any trace failure is the finding
        cell["violations"].append(f"trace failed: {type(e).__name__}: {e}")
        return cell

    avals = list(_avals(closed.jaxpr))
    dtypes = {str(getattr(a, "dtype", "")) for a in avals}
    eqns = [eqn for j in _walk(closed.jaxpr) for eqn in j.eqns]
    counts = collections.Counter(e.primitive.name for e in eqns)

    cell["n_eqns"] = sum(counts.values())
    cell["primitives"] = dict(sorted(counts.items()))
    cell["has_uint8"] = "uint8" in dtypes
    cell["has_f64"] = "float64" in dtypes
    weak = [str(a) for a in closed.out_avals if getattr(a, "weak_type", False)]
    cell["weak_outputs"] = weak

    in_dt, out_dt = _state_dtypes(state), _state_dtypes(out_shapes[0])
    cell["state_dtypes_preserved"] = in_dt == out_dt

    # packed-register cells must really carry uint8: the history rules
    # keep uint8 bitplanes in their state on every backend; the counter
    # rules expose a uint8 readout word only on the kernel datapaths
    rule_obj = plasticity.get_rule(rule)
    uint8_expected = rule_obj.has_sparse or backend != "reference"
    cell["uint8_expected"] = uint8_expected

    if cell["has_f64"]:
        cell["violations"].append("float64 aval in traced graph")
    if weak:
        cell["violations"].append(f"weak-typed outputs: {weak}")
    if not cell["state_dtypes_preserved"]:
        cell["violations"].append(f"state dtypes changed across the step: {in_dt} → {out_dt}")
    if uint8_expected and not cell["has_uint8"]:
        cell["violations"].append("no uint8 operand in a packed-register cell")
    return cell


def run_audit(kinds: Iterable[str] = KINDS) -> dict:
    cells = [audit_cell(rule, backend, kind) for rule, backend, kind in valid_cells(kinds)]
    return {
        "jax_version": jax.__version__,
        "kinds": list(kinds),
        "n_cells": len(cells),
        "n_violating": sum(1 for c in cells if c["violations"]),
        "cells": cells,
    }
