"""Unified observability layer: metrics registry, device-side scan event
counters, and Chrome-trace span tracing.

Everything is OFF by default (no-op fast paths); enable explicitly::

    from repro.obs import metrics, trace
    metrics.enable()   # counters / gauges / histograms + device event vector
    trace.enable()     # spans → Chrome trace JSON + repro.* profiler annotations

or per-plan via ``ExecutionPolicy(instrument=True)``.
"""
from repro.obs import metrics, trace
from repro.obs.metrics import (
    EVENT_NAMES,
    EVENT_VEC_LEN,
    EVT_MORSELS,
    EVT_PAUSES,
    EVT_PROBE_SATURATIONS,
    EVT_PROBE_STEPS,
    EVT_ROWS,
    EVT_ROWS_MASKED,
    NUM_EVENTS,
    PROBE_HIST_BUCKETS,
    PROBE_HIST_EDGES,
    PROBE_HIST_LABELS,
    EventPublisher,
    event_vector_to_dict,
    zero_event_vector,
)

__all__ = [
    "metrics",
    "trace",
    "EVENT_NAMES",
    "EVENT_VEC_LEN",
    "EVT_MORSELS",
    "EVT_PAUSES",
    "EVT_PROBE_SATURATIONS",
    "EVT_PROBE_STEPS",
    "EVT_ROWS",
    "EVT_ROWS_MASKED",
    "NUM_EVENTS",
    "PROBE_HIST_BUCKETS",
    "PROBE_HIST_EDGES",
    "PROBE_HIST_LABELS",
    "EventPublisher",
    "event_vector_to_dict",
    "zero_event_vector",
]
