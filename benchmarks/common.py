"""Shared benchmark machinery.

Datasets follow the paper's §4.1 exactly (scaled to container size):
cardinality ∈ {low: 1 000 uniques, high: 10% of N, unique: N} and
distribution ∈ {uniform, zipfian (s=0.8), heavy_hitter (50% one key)}.

Timing: jit + warmup, then median of R runs (the paper takes the median of
9 runs after warm-up), reported in µs per call.  Device-count scaling runs
in SUBPROCESSES with ``--xla_force_host_platform_device_count=k`` so the
main process keeps a single device (the paper's thread axis ⇒ simulated
device axis; wall-clock on 1 CPU core measures WORK, so scaling curves here
show algorithmic overhead, not real parallel speedup — EXPERIMENTS.md
discusses how to read them).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import jax
import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

N_ROWS = int(os.environ.get("BENCH_ROWS", 1 << 20))  # 1M rows default


def gen_keys(n: int, cardinality: str, dist: str, seed: int = 0) -> np.ndarray:
    rng = np.random.default_rng(seed)
    if cardinality == "low":
        k = 1000
    elif cardinality == "high":
        k = max(n // 10, 1)
    else:  # unique
        k = n
    if dist == "uniform":
        if cardinality == "unique":
            keys = rng.permutation(n).astype(np.uint32)
        else:
            keys = rng.integers(0, k, size=n).astype(np.uint32)
    elif dist == "zipf":
        z = rng.zipf(1.8 if cardinality == "low" else 1.0 + 0.8, size=n)
        keys = ((z - 1) % k).astype(np.uint32)
    elif dist == "heavy":
        keys = rng.integers(0, k, size=n).astype(np.uint32)
        hh = rng.random(n) < 0.5
        keys[hh] = 7
    else:
        raise ValueError(dist)
    return keys


def time_fn(fn, *args, warmup: int = 2, runs: int = 5) -> float:
    """Median latency in µs (jit-compatible fn; blocks on results)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(runs):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts) * 1e6)


def run_in_devices(k: int, code: str, env_extra=None) -> dict:
    """Run python code in a subprocess with k SIMULATED CPU devices; the
    code must print a single json line on stdout.  A CPU simulation by
    design: the child is held to the CPU (a parent that imported JAX may
    hold the accelerator, and a child reaching for it would fail or hang),
    and its result says so under ``"platform"``."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={k}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.update(env_extra or {})
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, env=env,
        timeout=1200,
    )
    if out.returncode != 0:
        raise RuntimeError(out.stderr[-2000:])
    res = json.loads(out.stdout.strip().splitlines()[-1])
    res["platform"] = "cpu"
    return res


def emit(name: str, us: float, derived: str = ""):
    print(f"{name},{us:.1f},{derived}", flush=True)


# -- unified gate schema (obs-backed) ----------------------------------------
#
# Every bench that writes a BENCH_*.json artifact routes it through
# write_bench_json: legacy top-level keys stay where report.py reads them,
# and the same numbers land under "metrics" plus explicit "gates" entries —
# the machine-checkable schema benchmarks/check_regression.py compares
# against the committed baselines.  When the obs registry is enabled the
# run's counter snapshot rides along under "obs".

_GATE_OPS = {
    "<=": lambda v, t: v <= t,
    ">=": lambda v, t: v >= t,
    "==": lambda v, t: v == t,
}


def gate(value, op: str, threshold):
    """One acceptance gate: ``{"value", "op", "threshold", "pass"}``."""
    return {
        "value": value,
        "op": op,
        "threshold": threshold,
        "pass": bool(_GATE_OPS[op](value, threshold)),
    }


def write_bench_json(path: str, bench: str, results: dict,
                     gates: dict | None = None) -> dict:
    """Write one bench artifact in the ``repro.obs/v1`` schema (legacy flat
    keys preserved at the top level) and return the payload."""
    from repro.obs import metrics as obs_metrics

    payload = dict(results)
    payload["bench"] = bench
    payload["schema"] = "repro.obs/v1"
    payload["metrics"] = {
        k: v for k, v in results.items()
        if isinstance(v, (int, float, bool)) and not isinstance(v, str)
    }
    payload["gates"] = gates or {}
    if obs_metrics.enabled():
        payload["obs"] = obs_metrics.snapshot()
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
    return payload
