"""Profiler traces: record a window, reduce it to device intervals, and
the arithmetic every per-layer reader shares (busy, idle, kernel time,
program time, gaps between programs).

A trace is reduced to a plain dict, the form the readers and the tests
use::

    {"window": [lo_ns, hi_ns],
     "devices": {"/device:TPU:0": {"ops": [[start_ns, dur_ns, tag], ...],
                                    "modules": [[start_ns, dur_ns, name],
                                                ...]}}}

``ops`` are the events of a device's "XLA Ops" line; ``tag`` is
``"kernel"`` for a Pallas (Mosaic) kernel, whose HLO is a custom call with
``custom_call_target="tpu_custom_call"``, and the op's HLO name otherwise.
``modules`` are the "XLA Modules" line: one event per executed program,
named ``jit_<function>(<hash>)``.  ``window`` is the host annotation
``WINDOW`` that the harness wraps around the traced work, on the same
clock, and ``host`` holds the harness's other annotations
(``chipbench.<phase>``) as ``[start_ns, dur_ns, name]``.
"""
from __future__ import annotations

import contextlib
import glob
import gzip
import json
import os

#: the host annotation around the traced work
WINDOW = "chipbench.window"
#: how the trace shows a Pallas kernel on a TPU
KERNEL_MARK = 'custom_call_target="tpu_custom_call"'


@contextlib.contextmanager
def record(directory: str):
    """Profile the block into ``directory`` (host tracer on, Python tracer
    off: it would record every Python call and swamp the trace)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(directory, profiler_options=opts):
        with jax.profiler.TraceAnnotation(WINDOW):
            yield


def xplane_path(directory: str) -> str:
    found = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise RuntimeError(f"the profiler wrote no trace under {directory}")
    return found[-1]


def reduce_xplane(path: str) -> dict:
    """An ``.xplane.pb`` file -> the reduced dict above."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, window, host = {}, None, []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for e in line.events:
                        tag = "kernel" if KERNEL_MARK in e.name \
                            else e.name.split(" ", 1)[0]
                        ops.append([e.start_ns, e.duration_ns, tag])
                elif line.name == "XLA Modules":
                    modules.extend([e.start_ns, e.duration_ns, e.name]
                                   for e in line.events)
            if ops or modules:
                devices[plane.name] = {"ops": ops, "modules": modules}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW:
                        window = [e.start_ns, e.start_ns + e.duration_ns]
                    elif e.name.startswith("chipbench."):
                        host.append([e.start_ns, e.duration_ns, e.name])
    if window is None:
        raise RuntimeError(f"no {WINDOW!r} annotation in {path}")
    if not devices:
        raise RuntimeError(f"no device events in {path}")
    return {"window": window, "devices": devices, "host": host}


def save(reduced: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(reduced, f)


def load(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


# --------------------------------------------------------------------------
# arithmetic on the reduced trace (nanoseconds in, nanoseconds out)
# --------------------------------------------------------------------------
def clip(events, lo: float, hi: float) -> list:
    """Events cut to [lo, hi], as (start, end, tag); those outside drop."""
    out = []
    for s, d, tag in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((a, b, tag))
    return out


def union_ns(spans) -> float:
    """Length of the union of (start, end, ...) spans."""
    total, cur_a, cur_b = 0.0, None, None
    for a, b, *_ in sorted(spans):
        if cur_b is None or a > cur_b:
            if cur_b is not None:
                total += cur_b - cur_a
            cur_a, cur_b = a, b
        else:
            cur_b = max(cur_b, b)
    if cur_b is not None:
        total += cur_b - cur_a
    return total


def window_ns(t: dict) -> float:
    lo, hi = t["window"]
    return hi - lo


def busy_ns(t: dict, device: str) -> float:
    """Time in the window in which an operation ran on ``device``."""
    lo, hi = t["window"]
    return union_ns(clip(t["devices"][device]["ops"], lo, hi))


def mean_busy_ns(t: dict) -> float:
    """Busy time averaged over the trace's devices."""
    devs = list(t["devices"])
    return sum(busy_ns(t, d) for d in devs) / len(devs)


def idle_pct(t: dict) -> float:
    """Share of the window in which the devices, on average, ran
    nothing."""
    return 100.0 * (1.0 - mean_busy_ns(t) / window_ns(t))


def kernel_ns(t: dict) -> float:
    """Device time of the Pallas kernels' events, over every device."""
    lo, hi = t["window"]
    return sum(b - a for dev in t["devices"].values()
               for a, b, tag in clip(dev["ops"], lo, hi) if tag == "kernel")


def module_ns(t: dict, function: str) -> float:
    """Device time of the programs jitted from ``function``."""
    lo, hi = t["window"]
    mark = f"jit_{function}("
    return sum(b - a for dev in t["devices"].values()
               for a, b, name in clip(dev["modules"], lo, hi)
               if name.startswith(mark))


#: ops that only contain other ops (a loop, a call) and would count twice
CONTAINERS = ("%while", "%conditional", "%call")


def top_ops(t: dict, n: int = 10) -> list:
    """The ``n`` device ops that took most time in the window, by tag,
    summed over devices: [[tag, seconds], ...]."""
    lo, hi = t["window"]
    total: dict = {}
    for dev in t["devices"].values():
        for a, b, tag in clip(dev["ops"], lo, hi):
            if not tag.startswith(CONTAINERS):
                total[tag] = total.get(tag, 0.0) + (b - a)
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[tag, ns / 1e9] for tag, ns in ranked]


def idle_gaps(t: dict, n: int = 10) -> list:
    """The ``n`` longest stretches of the window in which the first device
    ran nothing, each named by the harness annotation the host was in at
    its middle: [[name, seconds], ...]."""
    lo, hi = t["window"]
    dev = t["devices"][sorted(t["devices"])[0]]
    gaps, cur = [], lo
    for a, b, _ in sorted(clip(dev["ops"], lo, hi)):
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if hi > cur:
        gaps.append((cur, hi))
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = (a + b) / 2
        inside = [(s, name) for s, d, name in t.get("host", [])
                  if s <= mid <= s + d]
        name = max(inside)[1] if inside else "between annotations"
        out.append([name, (b - a) / 1e9])
    return out
