"""Device idle put down to program stages: the readers of
``planner_idle_share``, ``staging_idle_share`` and
``dispatch_idle_share`` on a synthetic trace, and the stages as a CPU
profile of the engine records them."""
from types import SimpleNamespace

import pytest

from chipbench import harness
from chipbench import span_idle
from chipbench import trace_reduce as T

MS = 1e6  # nanoseconds
IDLE_METRICS = ("planner_idle_share", "staging_idle_share", "dispatch_idle_share")


def _trace(devices=None):
    """A window of 10 ms.  The device runs 1-3 and 6-7 ms, so it idles
    0-1, 3-6 and 7-10 ms.  On the window's thread one chunk's ingest runs
    0-9 ms: the planner's sample 0.5-1.5, key combining 2-4, the dispatch
    4-5.5 with a runtime allocation 4.5-5 inside it.  Another thread holds
    a program span over 9-10 ms, where the window's thread is in none."""
    return T.Trace(
        devices=devices if devices is not None else {
            "/device:TPU:0": [("fusion.1", 1 * MS, 3 * MS),
                              ("while.2", 6 * MS, 7 * MS)]},
        host=[[(T.WINDOW, 0, 10 * MS), ("chipbench.query", 0, 10 * MS),
               ("repro.consume_async", 0, 9 * MS),
               ("repro.plan_sample", 0.5 * MS, 1.5 * MS),
               ("repro.combine_keys", 2 * MS, 4 * MS),
               ("repro.dispatch", 4 * MS, 5.5 * MS),
               ("DeferredTpuAllocator::Allocate", 4.5 * MS, 5 * MS)],
              [("repro.quantum", 8 * MS, 10 * MS)]],
    )


def _read(name, trace):
    ctx = SimpleNamespace(trace=trace, summary=T.summarize(trace)
                          if trace.devices else None)
    return harness.load_metric(name).read(ctx)


def test_stage_timeline_innermost_piece_by_piece():
    line = _trace().host[0]
    assert span_idle.stage_timeline(line) == [
        (0, 0.5 * MS, "repro.consume_async"),
        (0.5 * MS, 1.5 * MS, "repro.plan_sample"),
        (1.5 * MS, 2 * MS, "repro.consume_async"),
        (2 * MS, 4 * MS, "repro.combine_keys"),
        (4 * MS, 5.5 * MS, "repro.dispatch"),
        (5.5 * MS, 9 * MS, "repro.consume_async"),
    ]


def test_gap_split_by_overlap_not_midpoint():
    idle = span_idle.idle_by_stage(_trace())
    # the 3-6 ms gap runs across key combining, then the dispatch (whose
    # runtime allocation keeps no idle of its own), then consume_async
    assert idle == {"repro.consume_async": pytest.approx(0.5 * MS + 0.5 * MS + 2 * MS),
                    "repro.plan_sample": pytest.approx(0.5 * MS),
                    "repro.combine_keys": pytest.approx(1 * MS),
                    "repro.dispatch": pytest.approx(1.5 * MS)}
    assert sum(idle.values()) == pytest.approx(6 * MS)  # 9-10 ms: no stage


def test_idle_shares_and_their_sum():
    t = _trace()
    shares = {m: _read(m, t) for m in IDLE_METRICS}
    assert shares == {"planner_idle_share": pytest.approx(5.0),
                      "staging_idle_share": pytest.approx(10.0),
                      "dispatch_idle_share": pytest.approx(15.0)}
    assert _read("device_idle_share", t) == pytest.approx(70.0)
    assert sum(shares.values()) <= _read("device_idle_share", t)


def test_mean_over_device_planes():
    t = _trace(devices={
        "/device:TPU:0": [("fusion.1", 1 * MS, 3 * MS), ("while.2", 6 * MS, 7 * MS)],
        "/device:TPU:1": [("fusion.1", 0, 10 * MS)]})  # never idle
    assert _read("dispatch_idle_share", t) == pytest.approx(7.5)


@pytest.mark.parametrize("metric", IDLE_METRICS)
def test_nothing_to_read_without_a_device_plane(metric):
    assert _read(metric, _trace(devices={})) is None
    assert harness.load_metric(metric).read(SimpleNamespace(summary=None)) is None


def test_engine_stages_in_a_cpu_profile(tmp_path):
    """The engine's spans as the profiler records them on the CPU and
    ``trace_reduce.load`` reads them: in each chunk's ``consume_async`` on
    the window's thread, the four stages in order."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.engine import AggSpec, ExecutionPolicy, GroupByPlan, Table
    from repro.obs import trace as obs_trace

    keys = np.random.default_rng(3).integers(0, 50, 1024).astype(np.uint32)
    plan = GroupByPlan(keys=("k",), aggs=(AggSpec("count"),), raw_keys=True,
                       execution=ExecutionPolicy(morsel_rows=256))
    obs_trace.enable()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(T.WINDOW):
            plan.stream(Table({"k": jnp.asarray(keys[i:i + 256])})
                        for i in range(0, 1024, 256)).result()
    finally:
        jax.profiler.stop_trace()
        obs_trace.disable()
        obs_trace.clear()
    trace = T.load(T.find_xplane(str(tmp_path)))
    assert not trace.devices
    line = next(line for line in trace.host
                if any(name == T.WINDOW for name, _, _ in line))
    chunks = [(s, e) for name, s, e in line if name == "repro.consume_async"]
    assert len(chunks) == 4
    stages = ("repro.plan_sample", "repro.combine_keys", "repro.morselize",
              "repro.dispatch")
    for cs, ce in chunks:
        inside = sorted((s, name) for name, s, e in line
                        if name in stages and cs <= s and e <= ce)
        assert [name for _, name in inside] == list(stages)
    timeline = {name for _, _, name in span_idle.stage_timeline(line)}
    assert set(stages) <= timeline


@pytest.mark.parametrize("workload", [w["name"] for w in harness.load_benchmark()["workloads"]])
def test_traced_line_carries_planner_sample_share(workload, cpu_run):
    metrics = cpu_run(workload, trace=True)["metrics"]
    assert 0 < metrics["planner_sample_share"]["value"] < 100
    assert metrics["planner_sample_share"]["unit"] == "%"
