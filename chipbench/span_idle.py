"""Device idle time put down to the program stage the host was in.

Idle time is what :func:`chipbench.trace_reduce.summarize` counts: the
gaps of the union of a device plane's ``XLA Ops`` intervals inside the
benchmark's window, taken as the mean over device planes.  The program's
``obs.trace`` spans reach the profiler as ``repro.<name>`` annotations;
on the host line that holds the window they nest, and at each instant the
innermost one is the stage the host is in.  Each gap is split by overlap
over that timeline, so a gap that runs across several stages is shared
between them, and runtime events nested inside a stage leave its idle to
the stage.  Idle under no ``repro.*`` annotation goes to no stage.
"""
from __future__ import annotations

import math
from collections import defaultdict

from chipbench import trace_reduce as T

PREFIX = "repro."


def stage_timeline(line: list) -> list:
    """``(start, end, name)`` pieces of a host line, in order and apart,
    each labelled with the innermost ``repro.*`` event open over it; time
    under no such event is in no piece."""
    pieces, stack, t = [], [], -math.inf  # stack: (end, name), innermost last

    def advance(x):
        nonlocal t
        while stack and stack[-1][0] <= x:
            end, name = stack.pop()
            if end > t:
                pieces.append((t, end, name))
                t = end
        if stack and x > t:
            pieces.append((t, x, stack[-1][1]))
        t = max(t, x)

    for s, neg_e, name in sorted((s, -e, name) for name, s, e in line
                                 if name.startswith(PREFIX)):
        advance(s)
        stack.append((-neg_e, name))
    advance(math.inf)
    return pieces


def idle_by_stage(trace: T.Trace) -> dict:
    """Device idle nanoseconds under each innermost ``repro.*`` annotation
    of the window's host line, mean over device planes."""
    lo, hi = T.window_of(trace)
    line = next(line for line in trace.host
                if any(name == T.WINDOW for name, _, _ in line))
    pieces = stage_timeline(line)
    idle = defaultdict(float)
    for ops in trace.devices.values():
        merged = T.union(((s, e) for _, s, e in ops), lo, hi)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        i = 0
        for gs, ge in zip(edges[::2], edges[1::2]):
            while i < len(pieces) and pieces[i][1] <= gs:
                i += 1
            j = i
            while j < len(pieces) and pieces[j][0] < ge:
                s, e, name = pieces[j]
                idle[name] += min(e, ge) - max(s, gs)
                j += 1
    return {name: t / len(trace.devices) for name, t in idle.items()}


def share(ctx, stages: tuple) -> float | None:
    """Device idle under the given stages (span names without the prefix)
    over the window, in percent; None where the run kept no profiler trace
    (``ctx.trace``) or it holds no TPU device plane."""
    trace = getattr(ctx, "trace", None)
    if trace is None or not trace.devices:
        return None
    idle = idle_by_stage(trace)
    lo, hi = T.window_of(trace)
    return 100.0 * sum(idle.get(PREFIX + s, 0.0) for s in stages) / (hi - lo)
