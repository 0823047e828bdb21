"""Device time of the conv readout's im2col gather per raster.

The union of the traced slice's device intervals of ops whose ``op_name``
path holds the program's ``stdp.window`` scope (the gather of the packed
history words into patch rows, ``im2col_words_1d`` / ``im2col_words_2d``,
and its reshape to ``(M, K)``), divided by the slice's rasters.  The scope
sits inside ``stdp.timing`` and is none of ``_scopes.SCOPES``, so these ops
count in ``timing_device_ms`` too: this is a part of it.  The names are
mapped through the optimised HLO of the cell's ``run_snn``, compiled as
``_scopes`` compiles it.  A program without the scope (an fc network, an
inference program, or one that predates the scope) reads nothing.
"""
from __future__ import annotations

import functools
import json

from metrics import _scopes

# spelled out, as ``_scopes`` spells its names: programs without
# ``repro.tracing.WINDOW`` read nothing
WINDOW = "stdp.window"


def window_map(hlo_text: str) -> dict:
    """HLO instruction name -> ``WINDOW``, for the instructions under it."""
    return {name: WINDOW for name, op_name in _scopes.INSTRUCTION.findall(hlo_text)
            if WINDOW in op_name.split("/")}


@functools.lru_cache(maxsize=4)
def _hlo_text(config: str, train: bool, batch: int, t_steps: int) -> str:
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
        return harness.snn.run_snn.lower(state, raster, cfg, train=train).compile().as_text()


def read(run: dict) -> float | None:
    if run["trace"] is None:
        return None
    tr = run["traffic"]
    text = _hlo_text(json.dumps(run["config"], sort_keys=True), tr["mode"] == "train",
                     tr["batch"], tr["t_steps"])
    return _scopes.device_ms(run["trace"]["ops"], window_map(text), WINDOW,
                             run["window"].rasters)
