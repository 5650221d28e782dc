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


def stand_ins(monkeypatch) -> None:
    """What only a chip has, stood in for on the CPU: the look for a chip
    (the cell's first devices), its peaks, the tiny size, and the
    reduction of its device plane (a busy half of the window)."""
    import jax

    from chipbench import harness, peaks, trace_reduce
    from chipbench.tests.tiny import tiny

    real_load = harness.load_config

    def load_config(bench, name):
        cfg, mod = real_load(bench, name)
        cfg.update(tiny())
        return cfg, mod

    monkeypatch.setattr(harness, "check_chips", lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(harness, "load_config", load_config)
    monkeypatch.setattr(peaks, "peaks", lambda kind: peaks.PEAKS["TPU v5 lite"])
    monkeypatch.setattr(trace_reduce, "cell_planes", lambda trace, devices: trace)
    monkeypatch.setattr(trace_reduce, "summarize", lambda trace: trace_reduce.Summary(
        busy_s=1.0, window_s=2.0, device_ops=[], idle_gaps=[]))


@pytest.fixture
def cpu_run(monkeypatch, no_cache):
    """``run(workload, seed=7, seconds=0.0, trace=False)``: one run of a
    cell on the CPU at the tiny size, with :func:`stand_ins`."""
    from chipbench import harness

    stand_ins(monkeypatch)

    def run(workload, seed=7, seconds=0.0, trace=False):
        return harness.run(workload, seed, seconds, trace, t_start=time.perf_counter())

    return run
