"""Names of the program's profiler spans and device scopes.

Host spans are ``jax.profiler.TraceAnnotation`` events on the trace's host
plane, on the same clock as the device's ``XLA Ops`` events; they cost one
TraceMe check when no profiler runs.  Device scopes are ``jax.named_scope``
names in the jitted step: they land in each op's ``op_name`` metadata and
cost nothing at run time.  An op belongs to the innermost of the three
device scopes in its name stack, so ``TIMING`` may nest under ``UPDATE``.
"""
from __future__ import annotations

# host spans -----------------------------------------------------------------
RESET_DYNAMICS = "snn.reset_dynamics"    # the whole between-raster reset
INIT_SNN = "snn.init_snn"                # weight draw + fresh state, at set-up
FRESH_STATE = "snn.fresh_state"          # reset's cached fresh state built, once per (cfg, batch)
SAMPLE = "pipeline.sample"               # the sampler call of spike_stream
ENCODE = "pipeline.encode"               # min-max + Bernoulli rate coding
PREFETCH_PUT = "pipeline.prefetch.put"   # device_put plus enqueue
PREFETCH_WAIT_SPACE = "pipeline.prefetch.wait_space"  # producer on a full queue
PREFETCH_WAIT_ITEM = "pipeline.prefetch.wait_item"    # consumer on an empty queue

# device scopes ---------------------------------------------------------------
FORWARD = "snn.forward"      # currents, inhibition, neuron step, WTA, θ, pooling
TIMING = "stdp.timing"       # history pushes and every kernel readout of them
UPDATE = "stdp.update"       # update kernel, batch sum, clip, quantise

DEVICE_SCOPES = (FORWARD, TIMING, UPDATE)
# nested in TIMING and not one of the three, so its ops still count there;
# it lets the conv readout's gather read apart from the word pack before it
WINDOW = "stdp.window"       # im2col gather of the packed history words into patch rows
