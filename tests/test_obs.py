"""Observability tests (src/repro/obs/ + the engine-wide threading):

  * device-side event counters are EXACT — committed-row semantics under
    forced grow (pauses/migrations counted once, replayed morsels not
    double-counted), deterministic across identical runs, and bit-identical
    results vs the uninstrumented scan;
  * spill accounting parity: the registry series the SpillExecutor
    publishes equal the SpillManager's own counters, and the residency
    invariant (hot table never migrates) is visible in the counters;
  * span tracing emits valid Chrome-trace JSON with correctly nested spans,
    and puts each span on the JAX profiler's timeline as a ``repro.*``
    annotation (a chunk's stages inside its ``consume_async``);
  * ``QueryHandle.profile()`` under a 2-tenant DRR run reports queue wait,
    quanta, ingest progress and device bytes per tenant;
  * disabled mode (the default) emits nothing — empty registry, empty
    trace, no profiler annotation — while the unified ``stats()`` schema
    keeps every legacy key.
"""
import json

import numpy as np
import jax.numpy as jnp
import pytest

from repro.engine import (
    AggSpec,
    ExecutionPolicy,
    GroupByPlan,
    SaturationPolicy,
    Table,
)
from repro.engine.groupby import GroupByOperator
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace

N = 2048
CHUNK = 512


@pytest.fixture(autouse=True)
def _clean_obs():
    """Obs state is process-global: every test starts and ends dark so no
    other module's tests see counters or spans from here."""
    obs_metrics.disable()
    obs_metrics.clear()
    obs_trace.disable()
    obs_trace.clear()
    yield
    obs_metrics.disable()
    obs_metrics.clear()
    obs_trace.disable()
    obs_trace.clear()


def chunk_tables(keys, vals=None, chunk=CHUNK):
    for i in range(0, len(keys), chunk):
        cols = {"k": jnp.asarray(keys[i:i + chunk])}
        if vals is not None:
            cols["v"] = jnp.asarray(vals[i:i + chunk])
        yield Table(cols)


def table_map(out: Table) -> dict:
    n = int(out["__num_groups__"][0])
    return {int(k): float(v) for k, v in
            zip(np.asarray(out["key"])[:n], np.asarray(out["count(*)"])[:n])}


# ---------------------------------------------------------------------------
# device-side counter exactness


def _grow_op(**kw):
    kw.setdefault("collect_events", True)
    return GroupByOperator(
        key_columns=["k"], aggs=[AggSpec("count")], max_groups=16,
        morsel_rows=64, raw_keys=True, check_overflow=True, grow_bound=True,
        **kw,
    )


def test_event_counts_exact_under_forced_grow():
    keys = np.random.default_rng(0).permutation(256).astype(np.uint32)
    op = _grow_op()
    for i in range(0, 256, 64):
        op.consume(Table({"k": jnp.asarray(keys[i:i + 64])}))
    ev = op.event_counts()
    # committed-morsel semantics: every row counted EXACTLY once even
    # though paused morsels replay after migration
    assert ev["rows"] == 256
    assert ev["rows_masked"] == 0
    assert ev["morsels"] == 4
    assert ev["num_groups"] == 256
    assert sum(ev["probe_hist"]) == 256      # one bucket entry per row
    assert ev["probe_steps"] >= 256          # ≥1 slot inspection per row
    # 256 uniques against a bound of 16 MUST pause and grow
    assert ev["pauses"] >= 1
    assert ev["bound_grows"] >= 1
    assert ev["migrations"] >= 1
    assert ev["table_capacity"] >= 256
    assert 0.0 < ev["table_load_factor"] <= 1.0


def test_event_counts_deterministic_and_result_identical():
    keys = np.random.default_rng(1).permutation(256).astype(np.uint32)

    def run(collect):
        op = _grow_op(collect_events=collect)
        for i in range(0, 256, 64):
            op.consume(Table({"k": jnp.asarray(keys[i:i + 64])}))
        return op

    a, b, plain = run(True), run(True), run(False)
    assert a.event_counts() == b.event_counts()
    out_a, out_plain = a.finalize(), plain.finalize()
    for col in out_a.columns:
        assert np.array_equal(np.asarray(out_a[col]), np.asarray(out_plain[col]))
    # uninstrumented operators never allocate/transfer an event vector
    assert plain.event_counts()["rows"] == 0


def test_masked_rows_counted():
    op = GroupByOperator(
        key_columns=["k"], aggs=[AggSpec("count")], max_groups=64,
        morsel_rows=64, raw_keys=True, collect_events=True,
    )
    # 100 valid rows in a 128-row chunk: 28 rows pad to EMPTY inside the
    # morsel layout and must land in rows_masked, not rows
    op.consume(Table({"k": jnp.arange(100, dtype=jnp.uint32)}))
    ev = op.event_counts()
    assert ev["rows"] == 100
    assert ev["rows_masked"] == 28
    assert ev["morsels"] == 2


# ---------------------------------------------------------------------------
# registry + spill parity


def test_spill_registry_parity():
    obs_metrics.enable()
    rng = np.random.default_rng(7)
    keys = rng.integers(0, 1000, size=N).astype(np.uint32)
    plan = GroupByPlan(
        keys=("k",), aggs=(AggSpec("count"),), strategy="concurrent",
        max_groups=64, saturation=SaturationPolicy.SPILL, raw_keys=True,
        execution=ExecutionPolicy(morsel_rows=256, spill_partitions=8),
    )
    handle = plan.stream(chunk_tables(keys))
    handle.result()
    stats = handle.stats()            # publishes into the registry
    handle.stats()                    # idempotent: deltas, not re-adds
    snap = obs_metrics.snapshot()
    lbl = "strategy=spill"
    assert snap["counters"]["spill.spilled_rows"][lbl] == stats["spilled_rows"]
    assert snap["counters"]["spill.spilled_bytes"][lbl] == stats["spilled_bytes"]
    assert snap["counters"]["spill.readmitted_rows"][lbl] == (
        stats["readmitted_rows"])
    assert stats["spilled_rows"] > 0
    # nested section mirrors the flat compat keys
    assert stats["spill"]["spilled_rows"] == stats["spilled_rows"]
    assert stats["spill"]["residency_budget"] == stats["residency_budget"]
    # residency invariant, now counted: the hot table NEVER migrates
    assert stats["device"]["migrations"] == 0
    assert snap["counters"]["groupby.rows"][lbl] > 0


def test_probe_histogram_published():
    obs_metrics.enable()
    keys = np.random.default_rng(3).integers(0, 200, N).astype(np.uint32)
    plan = GroupByPlan(
        keys=("k",), aggs=(AggSpec("count"),), strategy="concurrent",
        max_groups=512, raw_keys=True,
    )
    handle = plan.stream(chunk_tables(keys))
    handle.result()
    stats = handle.stats()
    snap = obs_metrics.snapshot()
    hist = snap["histograms"]["groupby.probe_len"]["strategy=concurrent"]
    assert sum(hist["counts"]) == N
    assert hist["counts"] == stats["device"]["probe_hist"]
    gauges = snap["gauges"]
    assert gauges["groupby.table_load_factor"]["strategy=concurrent"] > 0


# ---------------------------------------------------------------------------
# tracing


def test_trace_valid_chrome_json_with_nested_spans():
    obs_trace.enable()
    keys = np.random.default_rng(5).permutation(N).astype(np.uint32)
    plan = GroupByPlan(  # tiny bound forces pause→migrate→resume spans
        keys=("k",), aggs=(AggSpec("count"),), strategy="concurrent",
        max_groups=32, saturation=SaturationPolicy.GROW, raw_keys=True,
        execution=ExecutionPolicy(morsel_rows=256),
    )
    handle = plan.stream(chunk_tables(keys))
    handle.result()
    payload = json.loads(json.dumps(obs_trace.to_json()))  # valid JSON
    events = payload["traceEvents"]
    assert payload["displayTimeUnit"] == "ms"
    for e in events:
        assert {"name", "ph", "ts", "pid", "tid"} <= set(e)
        if e["ph"] == "X":
            assert e["dur"] >= 0
    names = {e["name"] for e in events}
    assert {"pump", "consume_async", "poll",
            "pause_migrate_resume", "finalize"} <= names
    # nesting: every inner span sits inside a top-level pump/finalize span
    # (consume/poll run in the pump loop; in-flight drain + replay run
    # under finalize)
    tops = [e for e in events if e["name"] in ("pump", "finalize")]
    for e in events:
        if e["name"] in ("consume_async", "poll", "pause_migrate_resume"):
            assert any(
                t["ts"] <= e["ts"]
                and e["ts"] + e.get("dur", 0) <= t["ts"] + t["dur"]
                for t in tops
            ), e["name"]


def _profiled(tmp_path, run) -> list:
    """Run ``run()`` under the JAX profiler; returns the host timeline, one
    list of ``(name, start_ns, end_ns)`` per host thread."""
    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        run()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    data = ProfileData.from_file(str(path))
    return [[(e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events]
            for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines]


def _auto_stream():
    keys = np.random.default_rng(4).integers(0, 300, N).astype(np.uint32)
    plan = GroupByPlan(keys=("k",), aggs=(AggSpec("count"),), raw_keys=True,
                       execution=ExecutionPolicy(morsel_rows=256))
    return plan.stream(chunk_tables(keys)).result()


def test_spans_on_profiler_timeline(tmp_path):
    """Enabled spans land on the profiler's host timeline as ``repro.*``
    annotations: the per-chunk stages nest in ``consume_async`` on its
    thread (a grow replay's ``dispatch`` in ``pause_migrate_resume``), and
    the Chrome-trace events are still recorded."""
    obs_trace.enable()
    host = _profiled(tmp_path, _auto_stream)
    stages = ("plan_sample", "combine_keys", "morselize", "dispatch")
    nested = {name: 0 for name in stages}
    for line in host:
        parents = {n: [(s, e) for name, s, e in line if name == "repro." + n]
                   for n in ("consume_async", "pause_migrate_resume")}
        for name, s, e in line:
            stage = name.removeprefix("repro.")
            if not name.startswith("repro.") or stage not in stages:
                continue
            inside = [p for p, ivs in parents.items()
                      if any(ps <= s and e <= pe for ps, pe in ivs)]
            assert inside == ["consume_async"] or (
                stage == "dispatch" and inside == ["pause_migrate_resume"]
            ), (name, inside)
            nested[stage] += "consume_async" in inside
    chunks = N // CHUNK
    assert nested == {"plan_sample": chunks, "combine_keys": chunks,
                      "morselize": chunks, "dispatch": chunks}
    recorded = {e["name"] for e in obs_trace.events()}
    assert {"consume_async", *stages} <= recorded


def test_disabled_spans_leave_no_annotation(tmp_path, monkeypatch):
    """Disabled, ``span`` returns the shared no-op without building an
    annotation, and the profiler's timeline holds no ``repro.*`` event."""
    def no_annotation(*a, **k):
        raise AssertionError("a disabled span built an annotation")

    monkeypatch.setattr(obs_trace, "_annotation", no_annotation)
    assert obs_trace.span("dispatch") is obs_trace.span("plan_sample", k=1)
    host = _profiled(tmp_path, _auto_stream)
    assert not [name for line in host for name, _, _ in line
                if name.startswith("repro.")]
    assert obs_trace.events() == []


# ---------------------------------------------------------------------------
# per-query profiles (2-tenant DRR)


def test_query_profile_two_tenant_drr():
    from repro.serve.query_server import AggregationServer

    plan = GroupByPlan(
        keys=("k",), aggs=(AggSpec("count"),), strategy="concurrent",
        max_groups=128, raw_keys=True,
    )

    def source(seed, chunks=4):
        r = np.random.default_rng(seed)
        for _ in range(chunks):
            yield Table({"k": jnp.asarray(
                r.integers(0, 100, CHUNK).astype(np.uint32))})

    server = AggregationServer(slots=2, batch_queries=False)
    server.set_budget("alice", weight=2)
    server.set_budget("bob", weight=1)
    ha = server.submit(plan, source(1), tenant="alice")
    hb = server.submit(plan, source(2), tenant="bob")
    hc = server.submit(plan, source(3), tenant="bob")  # queues behind slots
    server.run_until_idle()
    for h, tenant in ((ha, "alice"), (hb, "bob"), (hc, "bob")):
        p = h.profile()
        assert p["tenant"] == tenant
        assert p["status"] == "done"
        assert p["chunks"] == 4
        assert p["rows"] == 4 * CHUNK
        assert p["quanta"] >= p["chunks"]
        assert p["wall_time_s"] > 0
        assert p["queue_wait_s"] >= 0
        assert p["device_table_bytes"] > 0
        assert p["stats"]["schema"] == "repro.obs/v1"
    # the third query waited for a slot: its queue time must be visible
    assert hc.profile()["queue_wait_s"] > 0
    ts = server.tenant_stats("bob")
    assert ts["quanta"] == ts["steps"] > 0
    assert ts["queue_depth"] == 0
    assert ts["queue_wait_s"] > 0


# ---------------------------------------------------------------------------
# disabled mode: no emissions, stats compat intact


def test_disabled_mode_emits_nothing():
    assert not obs_metrics.enabled() and not obs_trace.enabled()
    keys = np.random.default_rng(9).integers(0, 100, N).astype(np.uint32)
    plan = GroupByPlan(
        keys=("k",), aggs=(AggSpec("count"),), strategy="concurrent",
        max_groups=256, raw_keys=True,
    )
    handle = plan.stream(chunk_tables(keys))
    out = handle.result()
    stats = handle.stats()
    snap = obs_metrics.snapshot()
    assert snap["counters"] == {} and snap["gauges"] == {}
    assert snap["histograms"] == {}
    assert obs_trace.events() == []
    # the compat view: every pre-obs legacy key still at the top level
    for key in ("chunks_consumed", "rows_consumed", "peak_buffered_chunks",
                "peak_retained_bytes"):
        assert key in stats, key
    assert stats["chunks_consumed"] == N // CHUNK
    assert stats["rows_consumed"] == N
    assert stats["schema"] == "repro.obs/v1"
    # uninstrumented device section carries no event counters (no sync)
    assert "rows" not in stats["device"]
    assert table_map(out)  # the query itself is unaffected


def test_noop_objects_are_shared_and_inert():
    c = obs_metrics.counter("x.y", strategy="a")
    g = obs_metrics.gauge("x.z")
    h = obs_metrics.histogram("x.h", obs_metrics.PROBE_HIST_EDGES)
    assert c is g is h is obs_metrics.NOOP
    c.add(5)
    g.set(3)
    h.observe(1)
    assert obs_metrics.snapshot()["counters"] == {}
    s = obs_trace.span("nothing", k=1)
    with s:
        pass
    assert obs_trace.events() == []
