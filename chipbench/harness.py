"""Run one cell of ``BENCHMARK.json`` once.

Set-up makes the cell's data on the device from the seed and warms up with
one query of the cell; the window then drives the cell's traffic for
``--seconds``.  With ``--trace 0`` the result carries the cell's end-to-end
metrics; with ``--trace 1`` the window runs under the JAX profiler and the
program's ``obs.trace`` spans (nothing else is switched on) and the result
carries the cell's per-layer metrics.  A cell's ``chips`` are the devices
it gets, the devices whose peak memory it reports (the fullest) and the
device planes of the trace it reads.  After the window closes and the
peak memory is read, every query's result is compared with the
configuration's float64 reference.
"""
from __future__ import annotations

import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class NoChip(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


@dataclass
class Context:
    """What the metric readers read."""

    queries: list
    window_start: float
    window_end: float
    setup_s: float
    memory_peak_bytes: int | None  # the fullest of the cell's chips
    rows_per_query: int
    bytes_per_query: int
    peaks: dict            # one chip's published peaks
    chips: int             # the cell's chips, which share each query's work
    summary: object = None  # trace_reduce.Summary of a traced run
    trace: object = None    # its trace_reduce.Trace, the cell's planes only
    spans: list | None = None  # obs.trace events inside the window

    @property
    def window_us(self) -> float:
        return (self.window_end - self.window_start) * 1e6


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                   f"{[w['name'] for w in bench['workloads']]}")


def _module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_config(bench: dict, name: str):
    """A configuration's file of sizes and, beside it, its module of the
    same name (query, data maker, reference)."""
    entry = next(c for c in bench["configs"] if c["name"] == name)
    path = os.path.join(ROOT, entry["file"])
    with open(path) as f:
        cfg = json.load(f)
    return cfg, _module(os.path.splitext(path)[0] + ".py", f"chipbench_config_{name}")


def load_traffic(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        return json.load(f)


def load_metric(name: str):
    return _module(os.path.join(HERE, "metrics", f"{name}.py"),
                   f"chipbench_metric_{name}")


def metrics_for(bench: dict, workload: str, trace: bool) -> list:
    """The cell's end-to-end metrics, or with ``trace`` its per-layer ones."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if workload in m.get("workloads", [workload])]


def check_chips(chips: int) -> list:
    """The cell's devices: the first ``chips`` TPUs JAX finds.  A
    configuration laid out on several chips builds its mesh from the same
    ``jax.devices()[:chips]``."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU found (JAX platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found {len(devices)}")
    return devices[:chips]


def memory_peaks(devices) -> list:
    """Each device's allocator ``peak_bytes_in_use``, None where the
    backend reports none."""
    return [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at ``JAX_COMPILATION_CACHE_DIR``
    when set, else at the fixed ``<checkout>/.jax_cache``."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def _number(x):
    return x if x is None or math.isfinite(x) else None


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float) -> dict:
    """One run of one cell; returns the result line's object."""
    import jax

    bench = load_benchmark()
    w = cell(bench, workload)
    devices = check_chips(w["chips"])

    from chipbench import loadgen, peaks, trace_reduce
    from repro.obs import trace as obs_trace

    cache = enable_compile_cache()
    dev = devices[0]
    peak = peaks.peaks(dev.device_kind)
    cfg, config = load_config(bench, w["config"])
    traffic = load_traffic(w["traffic"])
    log(f"cell={workload} config={w['config']} traffic={w['traffic']} seed={seed} "
        f"seconds={seconds} trace={int(trace)} device={dev.device_kind} "
        f"count={len(devices)} ids={[d.id for d in devices]} jax={jax.__version__} "
        f"cache={cache}")

    data = config.make_data(cfg, seed)
    loadgen.warm(traffic, config, cfg, data)

    log_dir = None
    if trace:
        obs_trace.clear()
        obs_trace.enable()
        log_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
            queries = loadgen.run(traffic, config, cfg, data, seconds)
        t1 = max([q.end for q in queries] + [t0])
    finally:
        if trace:
            jax.profiler.stop_trace()
            obs_trace.disable()
    mem = memory_peaks(devices)
    reported = [b for b in mem if b is not None]
    ctx = Context(
        queries=queries, window_start=t0, window_end=t1, setup_s=t0 - t_start,
        memory_peak_bytes=max(reported) if reported else None,
        rows_per_query=cfg["lineitems"], bytes_per_query=config.bytes_per_query(cfg),
        peaks=peak, chips=len(devices),
    )
    out_device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(devices), "memory_peak_bytes": ctx.memory_peak_bytes,
                  "memory_peak_bytes_per_device": mem}
    breakdown = None
    if trace:
        ctx.spans = trace_reduce.spans_in(obs_trace.events(), t0 * 1e6, t1 * 1e6)
        obs_trace.clear()
        ctx.trace = trace_reduce.cell_planes(
            trace_reduce.load(trace_reduce.find_xplane(log_dir)), devices)
        ctx.summary = trace_reduce.summarize(ctx.trace)
        out_device.update(busy_s=ctx.summary.busy_s, window_s=ctx.summary.window_s)
        breakdown = {"device_ops": ctx.summary.device_ops,
                     "idle_gaps": ctx.summary.idle_gaps}
        shutil.rmtree(log_dir, ignore_errors=True)

    metrics = {}
    for m in metrics_for(bench, workload, trace):
        value = load_metric(m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": _number(float(value)), "unit": m["unit"]}

    # the comparison, after the window and the memory reading
    host_data = jax.device_get({c: data[c] for c in config.READS})
    del data
    t_ref = time.perf_counter()
    ref = config.reference(cfg, host_data)
    limits = cfg["limits"]
    worst = {name: 0.0 for name in limits}
    failed = 0
    for q in queries:
        if q.error is not None:
            failed += 1
            log(f"query failed: {q.error}")
            continue
        got = config.compare(q.result, ref)
        if any(not got[n] <= limits[n] for n in limits):
            failed += 1
        for n in limits:
            worst[n] = got[n] if not worst[n] >= got[n] else worst[n]
    checks = {n: {"value": _number(float(worst[n])), "limit": limits[n]}
              for n in limits}
    correct = bool(queries) and failed == 0 and all(
        c["value"] is not None and c["value"] <= c["limit"] for c in checks.values()
    )
    log(f"queries={len(queries)} failed={failed} reference_s="
        f"{time.perf_counter() - t_ref!r} window_s={t1 - t0!r}")
    for n, c in checks.items():
        log(f"check {n} = {c['value']!r} (limit {c['limit']!r})")

    result = {"correct": correct, "attempted": len(queries), "failed": failed,
              "metrics": metrics, "device": out_device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result
