"""Fixtures of the benchmark's CPU tests."""
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture
def no_cache(monkeypatch):
    """Keep the harness off JAX's persistent cache inside tests."""
    from chipbench import harness

    monkeypatch.setattr(harness, "enable_compile_cache", lambda: "off")


@pytest.fixture
def cpu_run(monkeypatch, no_cache):
    """``run(workload, seed=7, seconds=0.0, trace=False)``: one run of a
    cell on the CPU at the tiny size.  What only a chip has is stood in
    for: the look for a chip, its peaks, and the reduction of its device
    plane (a busy half of the window)."""
    import jax

    from chipbench import harness, peaks, trace_reduce
    from chipbench.tests.tiny import tiny

    real_load = harness.load_config

    def load_config(bench, name):
        cfg, mod = real_load(bench, name)
        cfg.update(tiny())
        return cfg, mod

    monkeypatch.setattr(harness, "check_chips", lambda chips: jax.devices())
    monkeypatch.setattr(harness, "load_config", load_config)
    monkeypatch.setattr(peaks, "peaks", lambda kind: peaks.PEAKS["TPU v5 lite"])
    monkeypatch.setattr(trace_reduce, "summarize", lambda trace: trace_reduce.Summary(
        busy_s=1.0, window_s=2.0, device_ops=[], idle_gaps=[]))

    def run(workload, seed=7, seconds=0.0, trace=False):
        return harness.run(workload, seed, seconds, trace, t_start=time.perf_counter())

    return run
