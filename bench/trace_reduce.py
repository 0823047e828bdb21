"""From a profiler trace to device busy time, kernel times and idle gaps.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
Device operations are the events of the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane; the harness's own host spans
(``jax.profiler.TraceAnnotation``) are events of the host plane, on the
same clock.  Everything below works on plain ``(name, start_ns, end_ns)``
tuples, so the recorded trace in ``tests/`` checks it without a chip.
"""
from __future__ import annotations

import collections
import glob
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
# control-flow ops span the ops they run; they are not work of their own
CONTAINER = re.compile(r"^%?(while|conditional|call)[.\d]*$")


def op_name(event_name: str) -> str:
    """``%name.12 = f32[...] custom-call(...)`` -> ``%name.12``."""
    return event_name.split(" = ", 1)[0]


def load(trace_dir: str) -> dict:
    """Device ops per chip and host spans, from the newest xplane file."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not files:
        raise FileNotFoundError(f"no xplane trace under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    devices: dict = {}
    host: list = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                devices[int(m.group(1))] = [
                    (op_name(e.name), e.start_ns, e.end_ns) for e in line.events
                    if not CONTAINER.match(op_name(e.name))]
            elif plane.name.startswith("/host:CPU"):
                host.extend((e.name, e.start_ns, e.end_ns) for e in line.events)
    return {"devices": devices, "host": host}


def union_ns(events: list) -> float:
    """Length of the union of the events' intervals."""
    total, end = 0.0, None
    for _, s, e in sorted(events, key=lambda x: x[1]):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def clip(events: list, t0: float, t1: float) -> list:
    """Events cut to the window [t0, t1]."""
    out = []
    for n, s, e in events:
        s, e = max(s, t0), min(e, t1)
        if e > s:
            out.append((n, s, e))
    return out


def gaps(events: list, t0: float, t1: float) -> list:
    """Idle intervals (start, end) of the device within [t0, t1]."""
    out, cur = [], t0
    for _, s, e in sorted(events, key=lambda x: x[1]):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if t1 > cur:
        out.append((cur, t1))
    return out


def kernel_events(events: list, pattern: str) -> list:
    rx = re.compile(pattern)
    return [ev for ev in events if rx.search(ev[0])]


def top_ops(events: list, n: int = 10) -> list:
    """The ops that took most device time, numbered instances merged."""
    tot: dict = collections.defaultdict(float)
    for name, s, e in events:
        tot[re.sub(r"\.\d+$", "", name)] += (e - s) / 1e9
    return sorted(([k, v] for k, v in tot.items()), key=lambda x: -x[1])[:n]


def label_gaps(idle: list, host: list, names: tuple, n: int = 10) -> list:
    """The longest idle gaps, each named by the host span of ``names``
    that covers most of it ("other" where none does)."""
    spans = [h for h in host if h[0] in names]
    out = []
    for s, e in sorted(idle, key=lambda g: g[0] - g[1])[:n]:
        best, cover = "other", 0.0
        for name, hs, he in spans:
            c = min(e, he) - max(s, hs)
            if c > cover:
                best, cover = name, c
        out.append([best, (e - s) / 1e9])
    return out


def reduce(trace: dict, t0: float, t1: float, host_names: tuple) -> dict:
    """Busy seconds per chip (mean), window seconds, per-chip clipped ops,
    the top device ops and the longest idle gaps of chip 0."""
    if not trace["devices"]:
        raise ValueError("the trace holds no device operations")
    clipped = {d: clip(ev, t0, t1) for d, ev in trace["devices"].items()}
    busy = [union_ns(ev) / 1e9 for ev in clipped.values()]
    first = clipped[min(clipped)]
    return {
        "busy_s": sum(busy) / len(busy),
        "window_s": (t1 - t0) / 1e9,
        "ops": first,
        "device_ops": top_ops(first),
        "idle_gaps": label_gaps(gaps(first, t0, t1), trace["host"], host_names),
    }


def host_window(host: list, name: str) -> tuple[float, float]:
    """Start and end of the host span ``name`` (the measured window)."""
    for n, s, e in host:
        if n == name:
            return s, e
    raise ValueError(f"no host span {name!r} in the trace")
