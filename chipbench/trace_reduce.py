"""From a profiler trace and the program's spans to the numbers the
per-layer metrics read.

Device side: the JAX profiler's ``.xplane.pb``.  Every TPU device plane's
``XLA Ops`` line gives the intervals in which an operation ran; their union
over the traced window is the device's busy time, and the gaps between
them are idle.  Each idle gap is labelled with the innermost host event
(the benchmark's own ``TraceAnnotation`` or JAX's dispatch and transfer
events) that covers its midpoint on the profiler's clock.  The harness
keeps the planes of the cell's own devices (:func:`cell_planes`), and every
number is a mean over those planes, so a cell on four chips reads per chip.

Host side: the ``obs.trace`` spans (Chrome trace events on
``perf_counter``, in microseconds).  A span's self time is its duration
less the spans nested directly inside it on the same thread.
"""
from __future__ import annotations

import bisect
import glob
import math
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
WINDOW = "chipbench.window"
_HLO_NAME = re.compile(r"^%?([^\s=]+)")
_MODULE_NAME = re.compile(r"^([^(]+)")


@dataclass
class Trace:
    """A profiler trace reduced to plain intervals, in nanoseconds.
    ``devices`` maps a device plane to its op events; ``host`` holds one
    list of ``(name, start, end)`` events per host thread."""

    devices: dict = field(default_factory=dict)
    host: list = field(default_factory=list)


@dataclass
class Summary:
    """Each number a mean over the trace's device planes."""

    busy_s: float        # union of op intervals in the window
    window_s: float
    device_ops: list     # [[name, seconds], ...], most time first
    idle_gaps: list      # [[host label, seconds], ...], most idle time first


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` written by ``jax.profiler``."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        data = ProfileData.from_serialized_xspace(f.read())
    trace = Trace()
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {line.name: list(line.events) for line in plane.lines}
            modules = sorted((e.start_ns, e.start_ns + e.duration_ns,
                              _MODULE_NAME.match(e.name).group(1))
                             for e in lines.get(MODULE_LINE, []))
            trace.devices[plane.name] = [
                (op_name(e.name, e.start_ns, modules), e.start_ns,
                 e.start_ns + e.duration_ns)
                for e in lines.get(OP_LINE, [])
            ]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                trace.host.append([(e.name, e.start_ns, e.start_ns + e.duration_ns)
                                   for e in line.events])
    return trace


def cell_planes(trace: Trace, devices) -> Trace:
    """``trace`` with only the device planes of ``devices``.  The plane
    ``/device:TPU:<n>`` is the device whose ``jax.Device.id`` is n: on a
    four-chip v5e host, programs launched on devices 0 to 3 (the chips at
    coords (0,0), (1,0), (0,1), (1,1)) run on planes 0 to 3.  A device with
    no plane in the trace is an error."""
    names = [f"/device:TPU:{d.id}" for d in devices]
    missing = [n for n in names if n not in trace.devices]
    if missing:
        raise ValueError(f"the trace holds no plane {missing}; it has "
                         f"{sorted(trace.devices)}")
    return Trace(devices={n: trace.devices[n] for n in names}, host=trace.host)


def op_name(hlo: str, start: float, modules: list) -> str:
    """``module/instruction`` for an ``XLA Ops`` event, whose name is the
    HLO instruction's text (``%fusion.5 = f32[...] fusion(...)``); the
    module is the ``XLA Modules`` event running at the op's start (sorted
    ``(start, end, name)``), without its fingerprint."""
    name = _HLO_NAME.match(hlo).group(1)
    i = bisect.bisect_right(modules, (start, math.inf)) - 1
    if i >= 0 and modules[i][0] <= start <= modules[i][1]:
        return f"{modules[i][2]}/{name}"
    return name


def window_of(trace: Trace) -> tuple[float, float]:
    """The traced window: the benchmark's ``chipbench.window`` annotation."""
    spans = [(s, e) for line in trace.host for name, s, e in line
             if name == WINDOW]
    if not spans:
        raise ValueError(f"the trace holds no {WINDOW!r} annotation")
    return min(s for s, _ in spans), max(e for _, e in spans)


def union(intervals, lo: float, hi: float) -> list:
    """Merged ``(start, end)`` intervals clipped to ``[lo, hi]``."""
    merged = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def labels(host: list, times: list) -> list:
    """For each time, the name of the innermost host event covering it.
    Events on one thread nest, so one sweep per thread keeps a stack whose
    top is that thread's innermost open event; across threads the shortest
    wins."""
    best = [None] * len(times)  # (duration, name)
    order = sorted(range(len(times)), key=times.__getitem__)
    for line in host:
        events = sorted((s, -e, name) for name, s, e in line if name != WINDOW)
        stack, i = [], 0
        for k in order:
            t = times[k]
            while i < len(events) and events[i][0] <= t:
                s, neg_e, name = events[i]
                while stack and stack[-1][1] < s:
                    stack.pop()
                stack.append((s, -neg_e, name))
                i += 1
            while stack and stack[-1][1] < t:
                stack.pop()
            if stack:
                s, e, name = stack[-1]
                if best[k] is None or e - s < best[k][0]:
                    best[k] = (e - s, name)
    return [b[1] if b else "(no host event)" for b in best]


def summarize(trace: Trace, top: int = 10) -> Summary:
    """Busy time, top ops and labelled idle gaps over the traced window."""
    if not trace.devices:
        raise ValueError("the trace holds no TPU device plane")
    lo, hi = window_of(trace)
    busy, op_time, gap_time = [], defaultdict(float), defaultdict(float)
    for ops in trace.devices.values():
        merged = union(((s, e) for _, s, e in ops), lo, hi)
        busy.append(sum(e - s for s, e in merged))
        for name, s, e in ops:
            op_time[name] += max(min(e, hi) - max(s, lo), 0.0)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
        for (s, e), name in zip(gaps, labels(trace.host,
                                             [(s + e) / 2 for s, e in gaps])):
            gap_time[name] += e - s
    ns = 1e-9
    return Summary(
        busy_s=sum(busy) / len(busy) * ns,
        window_s=(hi - lo) * ns,
        device_ops=[[n, t * ns / len(trace.devices)] for n, t in
                    sorted(op_time.items(), key=lambda kv: -kv[1])[:top]],
        idle_gaps=[[n, t * ns / len(trace.devices)] for n, t in
                   sorted(gap_time.items(), key=lambda kv: -kv[1])[:top]],
    )


def spans_in(events: list, lo_us: float, hi_us: float) -> list:
    """``obs.trace`` complete events that start inside ``[lo_us, hi_us]``."""
    return [e for e in events
            if e.get("ph") == "X" and lo_us <= e["ts"] <= hi_us]


def total_us(events: list, name: str) -> float:
    return float(sum(e["dur"] for e in events if e["name"] == name))


def self_us(events: list, name: str) -> float:
    """Summed self time of the spans called ``name``: each one's duration
    less that of the spans nested directly inside it on its thread."""
    by_tid = defaultdict(list)
    for e in events:
        by_tid[e["tid"]].append(e)
    total = 0.0
    for evs in by_tid.values():
        evs.sort(key=lambda e: (e["ts"], -e["dur"]))
        stack: list = []  # [event, child time]
        for e in evs:
            while stack and e["ts"] >= stack[-1][0]["ts"] + stack[-1][0]["dur"]:
                total += _close(stack.pop(), name)
            if stack:
                stack[-1][1] += e["dur"]
            stack.append([e, 0.0])
        while stack:
            total += _close(stack.pop(), name)
    return total


def _close(entry, name: str) -> float:
    e, child = entry
    return e["dur"] - child if e["name"] == name else 0.0
