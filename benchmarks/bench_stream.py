"""Streaming ingest benchmark — the PR's acceptance gates, measurable.

Three comparisons over the same multi-chunk stream:

  * ``stream_vs_oneshot`` — ``plan.collect(chunks)`` vs ``plan.run(table)``
    on the concurrent strategy (identical scan work; the streaming path
    must be ≈ parity);
  * ``overlap`` — double-buffered ingest (prefetch=2) vs fully synchronous
    ingest (prefetch=0) on the checked pipeline, with real host-side
    staging cost per chunk (the source generates its keys on demand) — the
    poll is the serialization point the prefetch window hides;
  * ``sharded`` — streaming carried-state ingest on simulated devices,
    reporting peak host RSS and the executor's retained-chunk high-water
    mark alongside wall-clock (run in its OWN subprocess so the RSS
    high-water is per-run).  Streaming is the only sharded ingest mode:
    the buffered gather-everything path was deleted once this benchmark
    showed streaming at parity with bounded memory.

Emits ``common.emit`` CSV; ``--json PATH`` additionally writes the raw
numbers as a JSON artifact (CI uploads ``BENCH_stream.json`` per PR to
track the perf trajectory).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np
import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.common import (
    N_ROWS,
    emit,
    gate,
    gen_keys,
    run_in_devices,
    time_fn,
    write_bench_json,
)
from repro.engine import AggSpec, ExecutionPolicy, GroupByPlan, SaturationPolicy, Table
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace

CHUNKS = 8

_SHARDED_CODE = """
import json, resource, time
import numpy as np, jax, jax.numpy as jnp
from repro.engine import AggSpec, ExecutionPolicy, GroupByPlan, SaturationPolicy, Table

n, chunks = %(n)d, %(chunks)d
rng = np.random.default_rng(3)
keys = rng.integers(0, 1000, size=n).astype(np.uint32)
vals = rng.normal(size=n).astype(np.float32)
mesh = jax.make_mesh((8,), ("data",))
plan = GroupByPlan(
    keys=("k",), aggs=(AggSpec("sum", "v"),), strategy="sharded",
    max_groups=1024, saturation=SaturationPolicy.UNCHECKED, raw_keys=True,
    execution=ExecutionPolicy(mesh=mesh, axis="data"),
)
step = n // chunks
def source():
    for i in range(0, n, step):
        yield Table({"k": jnp.asarray(keys[i:i+step]), "v": jnp.asarray(vals[i:i+step])})
# warmup (compile), then timed run
jax.block_until_ready(plan.collect(source()).columns)
t0 = time.perf_counter()
handle = plan.stream(source())
out = handle.result()
jax.block_until_ready(out.columns)
dt = time.perf_counter() - t0
print(json.dumps({
    "us": dt * 1e6,
    "peak_rss_mb": resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0,
    "peak_buffered_chunks": handle.peak_buffered_chunks,
    "groups": int(out["__num_groups__"][0]),
}))
"""


def _chunked(keys, vals, chunks=CHUNKS):
    step = keys.shape[0] // chunks
    for i in range(0, keys.shape[0], step):
        yield Table({"k": keys[i:i + step], "v": vals[i:i + step]})


def _staged_source(n, chunks, seed=5):
    """A source with real per-chunk host staging cost: keys are generated
    on demand (numpy RNG), the work the prefetch window overlaps with the
    in-flight device scan."""
    rng = np.random.default_rng(seed)
    step = n // chunks
    for _ in range(chunks):
        k = rng.integers(0, 10_000, size=step).astype(np.uint32)
        v = rng.normal(size=step).astype(np.float32)
        yield Table({"k": jnp.asarray(k), "v": jnp.asarray(v)})


def run(n: int | None = None, json_path: str | None = None,
        trace_path: str | None = None):
    n = n or N_ROWS
    results = {}
    rng = np.random.default_rng(3)
    keys = jnp.asarray(gen_keys(n, "low", "uniform"))
    vals = jnp.asarray(rng.normal(size=n).astype(np.float32))
    table = Table({"k": keys, "v": vals})

    # --- stream vs one-shot (concurrent, unchecked: the pure pipeline) ----
    plan = GroupByPlan(
        keys=("k",), aggs=(AggSpec("sum", "v"),), max_groups=1024,
        saturation=SaturationPolicy.UNCHECKED, raw_keys=True,
        strategy="concurrent",
    )
    us_one = time_fn(lambda: plan.run(table).columns)
    us_stream = time_fn(lambda: plan.collect(_chunked(keys, vals)).columns)
    results["oneshot_us"] = us_one
    results["stream_us"] = us_stream
    emit("stream_oneshot", us_one, f"n={n}")
    emit("stream_chunked", us_stream, f"chunks={CHUNKS}")
    emit("stream_vs_oneshot_ratio", us_stream / max(us_one, 1e-9), "≈1 expected")

    # --- overlap on/off (checked pipeline + host staging per chunk) -------
    grow_plan = GroupByPlan(
        keys=("k",), aggs=(AggSpec("sum", "v"),), max_groups=16_384,
        saturation=SaturationPolicy.GROW, raw_keys=True, strategy="concurrent",
    )
    for pf in (0, 2):
        # time_fn's warmup also pre-compiles the scan for this chunk shape
        us = time_fn(
            lambda pf=pf: grow_plan.stream(
                _staged_source(n, CHUNKS), prefetch=pf
            ).result().columns,
            warmup=1, runs=3,
        )
        results[f"overlap_prefetch{pf}_us"] = us
        emit(f"stream_prefetch{pf}", us, "double-buffered" if pf else "synchronous")
    results["overlap_speedup"] = (
        results["overlap_prefetch0_us"] / max(results["overlap_prefetch2_us"], 1e-9)
    )
    emit("stream_overlap_speedup", results["overlap_speedup"], ">1 = overlap pays")

    # --- instrumentation overhead A/B (the obs overhead guard) ------------
    # Same plan, same stream, three arms: obs off (baseline), obs fully on
    # (device event counters + span tracing + registry publishing), obs off
    # again (the no-op fast path must trace the IDENTICAL jitted program).
    # Executors resolve the instrument flag at construction, so flipping the
    # global switch between collect() calls is the whole A/B.
    # Arms are INTERLEAVED round-robin (off_a, on, off_b per round) so host
    # load drift hits every arm equally instead of landing in the ratio —
    # executors resolve the instrument flag at construction, so flipping the
    # global switch between collect() calls selects the arm.
    stream_fn = lambda: plan.collect(_chunked(keys, vals)).columns

    def _sample(instrumented: bool) -> float:
        if instrumented:
            obs_metrics.enable()
            obs_trace.enable()
        t0 = time.perf_counter()
        jax.block_until_ready(stream_fn())
        dt = time.perf_counter() - t0
        obs_trace.disable()
        obs_metrics.disable()
        return dt

    assert not obs_metrics.enabled()
    for instrumented in (False, True, True):  # warm/compile both programs
        _sample(instrumented)
    arms = {"off_a": [], "on": [], "off_b": []}
    for _ in range(7):
        arms["off_a"].append(_sample(False))
        arms["on"].append(_sample(True))
        arms["off_b"].append(_sample(False))
    # min, not median: the ratio of two IDENTICAL programs (off_a vs off_b)
    # measures pure host noise, and min is the stable latency estimator —
    # medians of interleaved arms still drifted ~6% on shared CI boxes
    us_off_a, us_on, us_off_b = (
        float(min(arms[a]) * 1e6) for a in ("off_a", "on", "off_b"))
    if trace_path:
        obs_metrics.enable()
        obs_trace.enable()
        # one clean instrumented pass so the artifact is a single stream's
        # spans, not the timing loop's pile-up
        obs_trace.clear()
        handle = plan.stream(_chunked(keys, vals))
        handle.result()
        obs_trace.save(trace_path)
        emit("stream_trace_artifact", len(obs_trace.events()),
             f"chrome-trace events -> {trace_path}")
        obs_trace.disable()
        obs_metrics.disable()
    us_off = (us_off_a + us_off_b) / 2.0
    results["obs_off_us"] = us_off
    results["obs_on_us"] = us_on
    results["obs_overhead_enabled"] = us_on / max(us_off, 1e-9)
    results["obs_overhead_disabled"] = us_off_b / max(us_off_a, 1e-9)
    emit("stream_obs_off", us_off, "uninstrumented baseline")
    emit("stream_obs_on", us_on, "device counters + tracing + registry")
    emit("stream_obs_overhead", results["obs_overhead_enabled"],
         "≤1.05 gate " + (
             "PASS" if results["obs_overhead_enabled"] <= 1.05 else "FAIL"))

    # --- §Operational: probe-length histogram + load factor by skew -------
    # The same instrumented plan over uniform vs zipfian keys: the histogram
    # shifts right as clustering grows probe chains — the paper's open-
    # addressing story, now measured from inside the jitted scan.
    obs_metrics.enable()
    operational = {}
    for dist in ("uniform", "zipf"):
        dkeys = jnp.asarray(gen_keys(n, "low", dist))
        handle = plan.stream(_chunked(dkeys, vals))
        handle.result()
        dev = handle.stats()["device"]
        operational[dist] = {
            "probe_hist": dev["probe_hist"],
            "probe_steps": dev["probe_steps"],
            "rows": dev["rows"],
            "table_load_factor": dev["table_load_factor"],
            "num_groups": dev["num_groups"],
        }
        mean_probe = dev["probe_steps"] / max(dev["rows"], 1)
        emit(f"stream_probe_mean_{dist}", mean_probe,
             f"load_factor={dev['table_load_factor']:.3f} "
             f"hist={dev['probe_hist']}")
    obs_metrics.disable()
    results["operational"] = operational

    # --- streaming sharded ingest (8 simulated devices) -------------------
    try:
        res = run_in_devices(
            8, _SHARDED_CODE % dict(n=min(n, 1 << 19), chunks=CHUNKS),
        )
    except RuntimeError as e:  # noqa: BLE001 — report, don't abort suite
        emit("stream_sharded_FAILED", -1,
             str(e).splitlines()[-1][:80].replace(",", ";"))
    else:
        results["sharded_stream"] = res
        emit(
            "stream_sharded", res["us"],
            f"rss={res['peak_rss_mb']:.0f}MB "
            f"buffered_chunks={res['peak_buffered_chunks']} "
            f"groups={res['groups']} platform={res['platform']}",
        )

    if json_path:
        results["n_rows"] = n
        results["chunks"] = CHUNKS
        # both gates carry the same ±5% host-noise headroom: off_a vs off_b
        # run the IDENTICAL program, so their ratio is pure measurement
        # noise (±4-6% even on interleaved mins on shared boxes) — the
        # deterministic "disabled = zero overhead" guarantee is enforced by
        # tests/test_obs.py (byte-identical scan, nothing emitted), and
        # this timing arm is the smoke check on top
        write_bench_json(json_path, "stream", results, gates={
            "obs_overhead_enabled": gate(
                results["obs_overhead_enabled"], "<=", 1.05),
            "obs_overhead_disabled": gate(
                results["obs_overhead_disabled"], "<=", 1.05),
        })
    return results


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None, help="write BENCH_stream.json here")
    ap.add_argument("--trace", default=None,
                    help="write a Perfetto-loadable chrome trace JSON here")
    ap.add_argument("--rows", type=int, default=None)
    args = ap.parse_args()
    print("name,us_per_call,derived", flush=True)
    run(n=args.rows, json_path=args.json, trace_path=args.trace)
