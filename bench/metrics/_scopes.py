"""Device time of the traced slice per program scope.

The program names three device scopes (``jax.named_scope``, see
``repro.tracing``): ``snn.forward``, ``stdp.timing``, ``stdp.update``.  An
op belongs to the innermost of them in its name stack.  The trace's ``XLA
Ops`` events carry only HLO instruction names (``%fusion.12``); the
optimised HLO text of the cell's ``run_snn`` maps each name to its
``op_name`` metadata.  That text is compiled after the window, in
``--trace 1`` runs only, from the same function, shapes and precision
the window ran, so the compile caches hand back the window's program.
(The program keys its persistent cache with the metadata, see
``repro.launch.compile_cache``; where a key leaves it out, the window
may run an executable compiled from another source, whose names and
scopes a compile of this source does not give.)  A scope's time is the
union of its ops' intervals, as busy time is.  The loop's small eager programs between rasters are
other modules: one of their ops that shares a name with a ``run_snn``
instruction counts in that instruction's scope.  A program without the
scopes reads nothing.
"""
from __future__ import annotations

import functools
import json
import re

from trace_reduce import union_ns

# the names of ``repro.tracing``, spelled out: the reader also runs against
# programs that predate that module, and reads nothing there
FORWARD, TIMING, UPDATE = "snn.forward", "stdp.timing", "stdp.update"
SCOPES = (FORWARD, TIMING, UPDATE)
INSTRUCTION = re.compile(r'^\s*(?:ROOT )?(%[^\s=]+) = .*?op_name="([^"]*)"', re.M)


def innermost(op_name: str) -> str | None:
    """The last of ``SCOPES`` in an ``op_name`` path, or None."""
    found = None
    for part in op_name.split("/"):
        if part in SCOPES:
            found = part
    return found


def scope_map(hlo_text: str) -> dict:
    """HLO instruction name -> scope, for the instructions under one."""
    out = {}
    for name, op_name in INSTRUCTION.findall(hlo_text):
        scope = innermost(op_name)
        if scope:
            out[name] = scope
    return out


def device_ms(ops: list, scopes: dict, scope: str, rasters: int) -> float | None:
    """Union of the device intervals of ``scope``'s ops, ms per raster."""
    ev = [e for e in ops if scopes.get(e[0]) == scope]
    if not ev or not rasters:
        return None
    return union_ns(ev) / 1e6 / rasters


@functools.lru_cache(maxsize=4)
def _compiled_map(config: str, train: bool, batch: int, t_steps: int) -> dict:
    import harness
    import jax
    import jax.numpy as jnp

    c = json.loads(config)
    cfg = harness.program_config(c)
    n_in = 1
    for d in cfg.input_shape:
        n_in *= d
    with harness.forward_precision(c):
        state = harness.snn.init_snn(jax.random.PRNGKey(0), cfg, batch)
        raster = jax.ShapeDtypeStruct((t_steps, batch, n_in), jnp.uint8)
        text = harness.snn.run_snn.lower(state, raster, cfg, train=train).compile().as_text()
    return scope_map(text)


def read(run: dict, scope: str) -> float | None:
    if run["trace"] is None:
        return None
    tr = run["traffic"]
    scopes = _compiled_map(json.dumps(run["config"], sort_keys=True), tr["mode"] == "train",
                           tr["batch"], tr["t_steps"])
    return device_ms(run["trace"]["ops"], scopes, scope, run["window"].rasters)
