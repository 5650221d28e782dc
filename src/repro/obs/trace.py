"""Span tracing → Chrome trace-event JSON (loadable in Perfetto / chrome://tracing)
and the JAX profiler's timeline.

Usage::

    from repro.obs import trace
    trace.enable()
    with trace.span("consume", chunk=3):
        ...
    trace.save("stream.trace.json")

Spans become ``"ph": "X"`` *complete* events (ts/dur in microseconds, the
format Perfetto's Chrome-trace importer expects).  Each span also opens a
``jax.profiler.TraceAnnotation`` named ``repro.<name>`` (its args become the
event's stats), so under a running ``jax.profiler`` trace the program's
spans sit on the profiler's clock next to the device ops they wait on.
Disabled (the default), :func:`span` returns a shared no-op context manager,
records nothing and creates no annotation — the hot path pays one ``if``.

The buffer is process-wide and thread-safe; ``pid``/``tid`` are real so
scheduler quanta from worker threads land on their own Perfetto tracks.
"""
from __future__ import annotations

import json
import os
import threading
import time

_enabled = False
_lock = threading.Lock()
_events: list = []
_annotation = None  # jax.profiler.TraceAnnotation, bound by enable()

PREFIX = "repro."  # of every span's profiler annotation


def enable() -> None:
    global _enabled, _annotation
    from jax.profiler import TraceAnnotation

    _annotation = TraceAnnotation
    _enabled = True


def disable() -> None:
    global _enabled
    _enabled = False


def enabled() -> bool:
    return _enabled


def clear() -> None:
    with _lock:
        _events.clear()


def events() -> list:
    with _lock:
        return list(_events)


def _now_us() -> int:
    return time.perf_counter_ns() // 1000


class _NoopSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NOOP_SPAN = _NoopSpan()


class _Span:
    __slots__ = ("name", "args", "t0", "annotation")

    def __init__(self, name, args):
        self.name, self.args = name, args
        self.annotation = _annotation(PREFIX + name, **args)

    def __enter__(self):
        self.annotation.__enter__()
        self.t0 = _now_us()
        return self

    def __exit__(self, *exc):
        end = _now_us()
        self.annotation.__exit__(*exc)
        ev = {
            "name": self.name,
            "ph": "X",
            "ts": self.t0,
            "dur": end - self.t0,
            "pid": os.getpid(),
            "tid": threading.get_ident(),
        }
        if self.args:
            ev["args"] = self.args
        with _lock:
            _events.append(ev)
        return False


def span(name: str, **args):
    """Context manager timing one span and annotating it on the profiler's
    timeline as ``repro.<name>``.  No-op (shared singleton) when disabled."""
    if not _enabled:
        return _NOOP_SPAN
    return _Span(name, args)


def to_json() -> dict:
    """The Chrome trace-event JSON object (``traceEvents`` container form)."""
    return {"traceEvents": events(), "displayTimeUnit": "ms"}


def save(path: str) -> str:
    """Write the trace to ``path``; returns the path."""
    with open(path, "w") as f:
        json.dump(to_json(), f)
    return path
