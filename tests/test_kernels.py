"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps,
plus the production fused route (``ExecutionPolicy.kernel="fused"``): parity
matrix vs the oracle, streaming ≡ one-shot, grow recovery, overflow, and the
kernel-selector API (aliases warn once, ``KERNELS`` validates)."""
import warnings

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import adaptive, groupby_oracle
from repro.engine import executors as executors_mod
from repro.engine.groupby import GroupByOverflowError
from repro.engine.plan_api import (
    KERNELS,
    AggSpec,
    ExecutionPolicy,
    GroupByPlan,
    arrays_as_table,
    execute,
)
from repro.kernels.fused_groupby import fused_groupby_pallas
from repro.kernels.ops import (
    groupby_kernel,
    groupby_pallas,
    multi_block_ticket,
    reset_deprecation_warnings,
    segment_aggregate,
    ticket,
)
from repro.kernels.ref import (
    fused_groupby_ref,
    segment_agg_ref,
    sort_ticket_ref,
    ticket_hash_ref,
)

RNG = np.random.default_rng(1)


@pytest.mark.parametrize("n,morsel,card", [
    (1024, 256, 64),
    (2048, 512, 500),
    (4096, 1024, 4096),   # unique-ish
    (1024, 1024, 8),      # single morsel, tiny cardinality
])
def test_ticket_kernel_bit_identical(n, morsel, card):
    keys = RNG.integers(0, card, size=n).astype(np.uint32)
    cap = 1 << (2 * card - 1).bit_length()
    t_k, kbt_k, cnt_k = ticket(jnp.asarray(keys), capacity=cap, max_groups=cap // 2,
                               morsel_size=morsel)
    t_r, kbt_r, cnt_r = ticket_hash_ref(jnp.asarray(keys), capacity=cap,
                                        max_groups=cap // 2, morsel_size=morsel)
    assert int(cnt_k) == int(cnt_r) == len(np.unique(keys))
    assert np.array_equal(np.asarray(t_k), np.asarray(t_r))
    assert np.array_equal(np.asarray(kbt_k)[: int(cnt_k)], np.asarray(kbt_r)[: int(cnt_r)])


def test_ticket_kernel_heavy_hitter():
    keys = RNG.integers(0, 300, size=2048).astype(np.uint32)
    keys[:1024] = 7
    t_k, _, cnt = ticket(jnp.asarray(keys), capacity=1024, max_groups=512, morsel_size=512)
    m = {}
    for k, t in zip(keys, np.asarray(t_k)):
        assert m.setdefault(int(k), int(t)) == int(t)
    assert int(cnt) == len(np.unique(keys))


@pytest.mark.parametrize("kind", ["sum", "count", "min", "max"])
@pytest.mark.parametrize("strategy", ["scatter", "onehot"])
def test_segment_kernel_matches_ref(kind, strategy):
    n, g = 2048, 300
    tickets = jnp.asarray(RNG.integers(-1, g, size=n).astype(np.int32))
    vals = jnp.asarray(RNG.normal(size=n).astype(np.float32))
    a_k = segment_aggregate(tickets, vals, num_groups=g, kind=kind,
                            strategy=strategy, morsel_size=512)
    a_r = segment_agg_ref(tickets, vals, num_groups=g, kind=kind)
    np.testing.assert_allclose(np.asarray(a_k), np.asarray(a_r), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [np.float32, np.float16])
def test_segment_kernel_dtypes(dtype):
    n, g = 1024, 100
    tickets = jnp.asarray(RNG.integers(0, g, size=n).astype(np.int32))
    vals = jnp.asarray(RNG.normal(size=n).astype(dtype))
    a_k = segment_aggregate(tickets, vals, num_groups=g, kind="sum", morsel_size=256)
    a_r = segment_agg_ref(tickets, vals, num_groups=g, kind="sum")
    np.testing.assert_allclose(np.asarray(a_k), np.asarray(a_r), rtol=2e-3, atol=2e-3)


def test_groupby_pallas_end_to_end():
    keys = RNG.integers(0, 400, size=4096).astype(np.uint32)
    vals = RNG.normal(size=4096).astype(np.float32)
    kbt, acc, cnt = groupby_pallas(jnp.asarray(keys), jnp.asarray(vals), kind="sum",
                                   max_groups=512, morsel_size=512)
    ref = groupby_oracle(jnp.asarray(keys), jnp.asarray(vals), kind="sum", max_groups=512)
    got = {int(k): float(v) for k, v in zip(np.asarray(kbt)[: int(cnt)], np.asarray(acc)[: int(cnt)])}
    want = {int(k): float(v) for k, v in
            zip(np.asarray(ref.keys)[: int(ref.num_groups)], np.asarray(ref.values)[: int(ref.num_groups)])}
    assert got.keys() == want.keys()
    for k in want:
        assert abs(got[k] - want[k]) < 1e-2


def test_multi_block_ticket_consistent():
    keys = RNG.integers(0, 3000, size=4096).astype(np.uint32)
    tb, _, _ = multi_block_ticket(jnp.asarray(keys), blocks=4, capacity_per_block=2048,
                                  max_groups_per_block=1024, morsel_size=1024)
    tb = np.asarray(tb)
    m = {}
    for k, t in zip(keys, tb):
        assert t >= 0
        assert m.setdefault(int(k), int(t)) == int(t)
    assert len(set(m.values())) == len(np.unique(keys))


def test_padding_is_noop():
    keys = RNG.integers(0, 100, size=1000).astype(np.uint32)  # 1000 % 256 != 0
    t, kbt, cnt = ticket(jnp.asarray(keys), capacity=512, max_groups=256, morsel_size=256)
    assert t.shape == (1000,)
    assert int(cnt) == len(np.unique(keys))

# ---------------------------------------------------------------------------
# fused VMEM-resident route (ExecutionPolicy.kernel="fused")


def _as_map(keys, vals, n):
    return {int(k): float(v) for k, v in zip(np.asarray(keys)[:n], np.asarray(vals)[:n])}


def _keys_for(dist, n, card, rng):
    if dist == "uniform":
        return rng.integers(0, card, size=n).astype(np.uint32)
    if dist == "zipf":
        return (rng.zipf(1.3, size=n) % card).astype(np.uint32)
    # near-unique: every key appears once or twice
    return rng.choice(n, size=n, replace=True).astype(np.uint32)


def _run_plan(keys, vals, aggs, **kw):
    table, _ = arrays_as_table(jnp.asarray(keys), jnp.asarray(vals))
    plan = GroupByPlan(
        keys=("__key__",), aggs=aggs,
        strategy=kw.pop("strategy", "concurrent"),
        max_groups=kw.pop("max_groups", 1024),
        saturation=kw.pop("saturation", "raise"), raw_keys=True,
        execution=ExecutionPolicy(morsel_size=kw.pop("morsel_size", 512), **kw),
    )
    return execute(plan, table)


def _result_map(out, col):
    n = int(out["__num_groups__"][0])
    return _as_map(out["key"], out[col], n)


@pytest.mark.parametrize("dist", ["uniform", "zipf", "near_unique"])
@pytest.mark.parametrize("kind", ["sum", "count", "min", "max", "mean"])
def test_fused_route_parity_matrix(dist, kind):
    """Fused route vs the scan pipeline over the distribution × aggregate
    matrix, through the one executor seam both share."""
    rng = np.random.default_rng(hash((dist, kind)) % (1 << 31))
    n, card = 4096, 300 if dist != "near_unique" else 4096
    keys = _keys_for(dist, n, card, rng)
    vals = rng.normal(size=n).astype(np.float32)
    agg = AggSpec("count") if kind == "count" else AggSpec(kind, "v")
    bound = 8192 if dist == "near_unique" else 1024
    got = _run_plan(keys, vals, (agg,), kernel="fused", max_groups=bound)
    ref = _run_plan(keys, vals, (agg,), kernel="off", max_groups=bound)
    assert int(got["__num_groups__"][0]) == int(ref["__num_groups__"][0])
    g, r = _result_map(got, agg.name), _result_map(ref, agg.name)
    assert g.keys() == r.keys()
    for k in r:
        assert abs(g[k] - r[k]) < 1e-2, (dist, kind, k)


@pytest.mark.parametrize("kind", ["sum", "count", "min", "max"])
def test_fused_kernel_matches_oracle(kind):
    keys = RNG.integers(0, 300, size=4096).astype(np.uint32)
    vals = RNG.normal(size=4096).astype(np.float32)
    kbt, acc, cnt = fused_groupby_pallas(
        jnp.asarray(keys), jnp.asarray(vals), capacity=1024, max_groups=512,
        kind=kind, morsel_size=512,
    )
    ref = groupby_oracle(jnp.asarray(keys), jnp.asarray(vals), kind=kind, max_groups=512)
    got = _as_map(kbt, acc, int(cnt))
    want = _as_map(ref.keys, ref.values, int(ref.num_groups))
    assert got.keys() == want.keys()
    for k in want:
        assert abs(got[k] - want[k]) < 1e-2, (kind, k)


def test_fused_kernel_matches_two_phase():
    """Fused must agree with the two-kernel pipeline bit-for-bit on tickets
    (same protocol) and allclose on aggregates."""
    keys = RNG.integers(0, 200, size=2048).astype(np.uint32)
    vals = RNG.normal(size=2048).astype(np.float32)
    kbt_f, acc_f, cnt_f = fused_groupby_pallas(
        jnp.asarray(keys), jnp.asarray(vals), capacity=512, max_groups=256,
        kind="sum", morsel_size=512,
    )
    kbt_2, acc_2, cnt_2 = groupby_kernel(
        jnp.asarray(keys), jnp.asarray(vals), kind="sum", max_groups=256,
        capacity=512, morsel_size=512, saturation="unchecked",
    )
    assert int(cnt_f) == int(cnt_2)
    assert np.array_equal(np.asarray(kbt_f)[: int(cnt_f)],
                          np.asarray(kbt_2)[: int(cnt_2)].astype(np.uint32))
    np.testing.assert_allclose(
        np.asarray(acc_f)[: int(cnt_f)], np.asarray(acc_2)[: int(cnt_2)],
        rtol=1e-5, atol=1e-5,
    )


def test_fused_kernel_matches_ref_bit_identical():
    """fused_groupby_ref replays the identical morsel walk, so tickets (and
    hence key_by_ticket order) and float sums must match bit-for-bit."""
    keys = RNG.integers(0, 500, size=4096).astype(np.uint32)
    vals = RNG.normal(size=4096).astype(np.float32)
    kbt_k, acc_k, cnt_k = fused_groupby_pallas(
        jnp.asarray(keys), jnp.asarray(vals), capacity=2048, max_groups=1024,
        kind="sum", morsel_size=512,
    )
    kbt_r, accs_r, cnt_r = fused_groupby_ref(
        jnp.asarray(keys), jnp.asarray(vals)[None, :], capacity=2048,
        max_groups=1024, specs=((0, "sum"),), morsel_size=512,
    )
    n = int(cnt_k)
    assert n == int(cnt_r)
    assert np.array_equal(np.asarray(kbt_k)[:n], np.asarray(kbt_r)[:n])
    assert np.array_equal(np.asarray(acc_k)[:n], np.asarray(accs_r)[0, :n])


def test_fused_streaming_equals_oneshot():
    """Chunked consume through the carried VMEM table must be BIT-exact with
    one-shot consume: the morsel walk is identical when chunks split on
    morsel boundaries."""
    keys = RNG.integers(0, 400, size=8192).astype(np.uint32)
    vals = RNG.normal(size=8192).astype(np.float32)
    aggs = (AggSpec("sum", "v"), AggSpec("mean", "v"), AggSpec("max", "v"))
    plan = GroupByPlan(
        keys=("__key__",), aggs=aggs, strategy="concurrent", max_groups=512,
        saturation="raise", raw_keys=True,
        execution=ExecutionPolicy(kernel="fused", morsel_size=512),
    )
    one = executors_mod.make_executor(plan)
    table, _ = arrays_as_table(jnp.asarray(keys), jnp.asarray(vals))
    one.consume(table)
    oneshot = one.finalize()
    chunked = executors_mod.make_executor(plan)
    for lo in range(0, 8192, 2048):
        t, _ = arrays_as_table(
            jnp.asarray(keys[lo:lo + 2048]), jnp.asarray(vals[lo:lo + 2048])
        )
        chunked.consume(t)
    streamed = chunked.finalize()
    n = int(oneshot["__num_groups__"][0])
    assert n == int(streamed["__num_groups__"][0])
    for col in ("key", "sum(v)", "mean(v)", "max(v)"):
        assert np.array_equal(
            np.asarray(oneshot[col])[:n], np.asarray(streamed[col])[:n]
        ), col


def test_fused_grow_recovers_undersized_bound():
    """Forced-undersized bound AND capacity: the §4.4 pause → host grow →
    resume loop must recover exact results without replaying the stream."""
    keys = RNG.integers(0, 700, size=8192).astype(np.uint32)
    vals = RNG.normal(size=8192).astype(np.float32)
    agg = (AggSpec("sum", "v"),)
    got = _run_plan(keys, vals, agg, kernel="fused", max_groups=32,
                    capacity=64, saturation="grow")
    ref = _run_plan(keys, vals, agg, kernel="off", max_groups=4096)
    assert int(got["__num_groups__"][0]) == int(ref["__num_groups__"][0])
    g, r = _result_map(got, "sum(v)"), _result_map(ref, "sum(v)")
    assert g.keys() == r.keys()
    for k in r:
        assert abs(g[k] - r[k]) < 1e-2


def test_fused_grow_streaming_prefetch_exact():
    """GROW while chunks are in flight: prefetch dispatches chunk k+1
    before chunk k's poll, so the pause must replay EVERY pending launch
    from its own recorded halt morsel.  A single last-chunk replay slot
    silently drops the earlier chunk's unreplayed tail (rows lost, counts
    low) — this pins the pending-launch queue."""
    keys = RNG.integers(0, 600, size=8192).astype(np.uint32)
    vals = np.ones(8192, dtype=np.float32)
    plan = GroupByPlan(
        keys=("__key__",), aggs=(AggSpec("count"), AggSpec("sum", "v")),
        strategy="concurrent", max_groups=64, saturation="grow",
        raw_keys=True,
        execution=ExecutionPolicy(kernel="fused", morsel_size=1024),
    )

    def chunks():
        for lo in range(0, 8192, 1024):
            t, _ = arrays_as_table(jnp.asarray(keys[lo:lo + 1024]),
                                   jnp.asarray(vals[lo:lo + 1024]))
            yield t

    out = plan.stream(chunks()).result()
    n = int(out["__num_groups__"][0])
    ref_k, ref_c = np.unique(keys, return_counts=True)
    assert n == ref_k.shape[0]
    got_counts = {k: int(v) for k, v in _result_map(out, "count(*)").items()}
    assert got_counts == dict(zip(ref_k.tolist(), ref_c.tolist()))
    assert int(np.asarray(out["count(*)"])[:n].sum()) == 8192


def test_fused_overflow_raises():
    keys = np.arange(2048, dtype=np.uint32)
    vals = np.ones(2048, dtype=np.float32)
    with pytest.raises(GroupByOverflowError):
        _run_plan(keys, vals, (AggSpec("sum", "v"),), kernel="fused",
                  max_groups=64, saturation="raise")


def test_fused_two_level_programs_merge():
    """programs>1: per-grid-program local tables + second-level merge must
    agree with the single-table result."""
    keys = RNG.integers(0, 300, size=8192).astype(np.uint32)
    vals = RNG.normal(size=8192).astype(np.float32)
    agg = (AggSpec("sum", "v"),)
    got = _run_plan(keys, vals, agg, kernel="fused", kernel_programs=4)
    ref = _run_plan(keys, vals, agg, kernel="off")
    assert int(got["__num_groups__"][0]) == int(ref["__num_groups__"][0])
    g, r = _result_map(got, "sum(v)"), _result_map(ref, "sum(v)")
    assert g.keys() == r.keys()
    for k in r:
        assert abs(g[k] - r[k]) < 1e-2


# ---------------------------------------------------------------------------
# kernel-selector API: ExecutionPolicy.kernel is the ONE selector


def test_kernel_selector_validates():
    with pytest.raises(ValueError):
        GroupByPlan(keys=("k",), aggs=(AggSpec("count"),),
                    execution=ExecutionPolicy(kernel="bogus"))
    with pytest.raises(ValueError):
        GroupByPlan(keys=("k",), aggs=(AggSpec("count"),),
                    execution=ExecutionPolicy(kernel_programs=0))
    assert set(KERNELS) == {None, "off", "scan_body", "split", "fused"}


def test_kernel_selector_rejects_bad_combinations():
    for bad in (
        dict(strategy="hybrid", execution=ExecutionPolicy(kernel="fused")),
        dict(strategy="concurrent", saturation="spill",
             execution=ExecutionPolicy(kernel="fused")),
        dict(strategy="concurrent",
             execution=ExecutionPolicy(kernel="split", ticketing="sort")),
    ):
        plan = GroupByPlan(keys=("k",), aggs=(AggSpec("count"),),
                           max_groups=64, **bad)
        with pytest.raises(ValueError):
            executors_mod.make_executor(plan)


def test_strategy_pallas_alias_warns_once_and_matches():
    keys = RNG.integers(0, 200, size=4096).astype(np.uint32)
    vals = RNG.normal(size=4096).astype(np.float32)
    agg = (AggSpec("sum", "v"),)
    executors_mod.reset_kernel_alias_warnings()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        old = _run_plan(keys, vals, agg, strategy="pallas", max_groups=256)
        _run_plan(keys, vals, agg, strategy="pallas", max_groups=256)
    dep = [w for w in caught if issubclass(w.category, DeprecationWarning)]
    assert len(dep) == 1 and "kernel='split'" in str(dep[0].message)
    new = _run_plan(keys, vals, agg, kernel="split", max_groups=256)
    n = int(old["__num_groups__"][0])
    assert n == int(new["__num_groups__"][0])
    assert _result_map(old, "sum(v)") == _result_map(new, "sum(v)")


def test_use_kernel_alias_warns_once_and_matches():
    keys = RNG.integers(0, 200, size=4096).astype(np.uint32)
    vals = RNG.normal(size=4096).astype(np.float32)
    agg = (AggSpec("sum", "v"),)
    executors_mod.reset_kernel_alias_warnings()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        old = _run_plan(keys, vals, agg, use_kernel=True, max_groups=256)
        _run_plan(keys, vals, agg, use_kernel=True, max_groups=256)
    dep = [w for w in caught if issubclass(w.category, DeprecationWarning)]
    assert len(dep) == 1 and "kernel='scan_body'" in str(dep[0].message)
    new = _run_plan(keys, vals, agg, kernel="scan_body", max_groups=256)
    n = int(old["__num_groups__"][0])
    assert n == int(new["__num_groups__"][0])
    assert _result_map(old, "sum(v)") == _result_map(new, "sum(v)")


def test_direct_entry_points_warn_once():
    keys = jnp.asarray(RNG.integers(0, 64, size=1024).astype(np.uint32))
    reset_deprecation_warnings()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        ticket(keys, capacity=256, max_groups=128)
        ticket(keys, capacity=256, max_groups=128)
        segment_aggregate(jnp.zeros(1024, jnp.int32), jnp.ones(1024),
                          num_groups=8)
        groupby_pallas(keys, kind="count", max_groups=128)
    dep = [w for w in caught if issubclass(w.category, DeprecationWarning)]
    assert len(dep) == 3  # one per alias, not per call
    assert all("ExecutionPolicy.kernel" in str(w.message) for w in dep)


def test_fused_is_batching_ineligible():
    base = dict(keys=("k",), aggs=(AggSpec("count"),), strategy="concurrent",
                max_groups=64, saturation="raise")
    eligible = GroupByPlan(**base)
    assert executors_mod.batch_signature(eligible) is not None
    for k in ("scan_body", "split", "fused"):
        plan = GroupByPlan(**base, execution=ExecutionPolicy(kernel=k))
        assert executors_mod.batch_signature(plan) is None, k


# ---------------------------------------------------------------------------
# planner: choose_plan picks "fused" when the table fits the VMEM budget


def test_choose_plan_fused_on_vmem_fit():
    stats = adaptive.WorkloadStats(n_rows=1_000_000, est_groups=1000,
                                   est_top_freq=0.0)
    assert adaptive.choose_plan(stats, vmem_budget=4 << 20).kernel == "fused"
    assert adaptive.choose_plan(stats, vmem_budget=1024).kernel is None
    big = adaptive.WorkloadStats(n_rows=10_000_000, est_groups=500_000,
                                 est_top_freq=0.0)
    assert adaptive.choose_plan(big, vmem_budget=4 << 20).kernel is None
    # more accumulators -> bigger footprint -> the fit can flip
    mid = adaptive.WorkloadStats(n_rows=1_000_000, est_groups=30_000,
                                 est_top_freq=0.0)
    one = adaptive.fused_table_bytes(2 * mid.est_groups, 1)
    assert adaptive.choose_plan(mid, vmem_budget=one + 8 * mid.est_groups + 1,
                                num_accumulators=1).kernel == "fused"
    assert adaptive.choose_plan(mid, vmem_budget=one,
                                num_accumulators=4).kernel is None


def test_resolver_adopts_fused_under_budget(monkeypatch):
    """strategy='auto' resolves kernel='fused' when the planner's VMEM
    budget admits the estimated table (budget forced, since interpret-mode
    CPUs report 0)."""
    monkeypatch.setattr(adaptive, "kernel_table_budget", lambda: 4 << 20)
    keys = RNG.integers(0, 200, size=4096).astype(np.uint32)
    stats = adaptive.sample_stats(jnp.asarray(keys))
    plan = GroupByPlan(keys=("__key__",), aggs=(AggSpec("count"),),
                       strategy="auto", raw_keys=True)
    resolved = executors_mod.resolve_plan_stats(
        executors_mod.normalize_kernel(plan), stats
    )
    assert resolved.execution.kernel == "fused"
    assert isinstance(executors_mod.make_executor(resolved),
                      executors_mod._FusedExecutor)
    # an explicit kernel choice always wins over the planner
    pinned = GroupByPlan(keys=("__key__",), aggs=(AggSpec("count"),),
                         strategy="auto", raw_keys=True,
                         execution=ExecutionPolicy(kernel="off"))
    assert executors_mod.resolve_plan_stats(
        pinned, stats
    ).execution.kernel == "off"


# ---------------------------------------------------------------------------
# TPU backend: no route reaches a kernel the chip's compiler refuses


@pytest.fixture
def tpu_backend(monkeypatch):
    """Make the planner and executors see a v5e TPU backend (nothing here
    compiles or runs a kernel)."""
    import types

    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "devices", lambda *a: [
        types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    ])


def test_tpu_planner_never_picks_fused(tpu_backend):
    assert adaptive.kernel_table_budget() == (128 << 20) // 4
    stats = adaptive.WorkloadStats(n_rows=1_000_000, est_groups=1000,
                                   est_top_freq=0.0)
    assert adaptive.choose_plan(stats).kernel is None
    assert adaptive.choose_plan(stats, vmem_budget=1 << 30).kernel is None
    plan = GroupByPlan(keys=("__key__",), aggs=(AggSpec("count"),),
                       strategy="auto", raw_keys=True)
    resolved = executors_mod.resolve_plan_stats(plan, stats)
    assert resolved.execution.kernel is None
    assert resolved.execution.update == "onehot"


@pytest.mark.parametrize("kernel,update,refused", [
    ("fused", None, "Only 2D gather is supported"),
    ("split", None, "Only 2D gather is supported"),
    ("scan_body", "scatter", "scatter-add"),
    ("scan_body", None, "scatter-add"),
    ("scan_body", "onehot", None),
    ("off", None, None),
])
def test_tpu_explicit_refused_kernel_raises(tpu_backend, kernel, update,
                                            refused):
    plan = GroupByPlan(keys=("__key__",), aggs=(AggSpec("count"),),
                       strategy="concurrent", max_groups=1024, raw_keys=True,
                       execution=ExecutionPolicy(kernel=kernel, update=update))
    if refused is None:
        executors_mod.make_executor(plan)
        return
    with pytest.raises(NotImplementedError, match=refused):
        executors_mod.make_executor(plan)
    # auto plans pinned to a refused kernel raise at make_executor too
    if kernel in ("fused", "split"):
        with pytest.raises(NotImplementedError, match=refused):
            executors_mod.make_executor(plan.with_(strategy="auto"))


def test_vmem_budget_refuses_unknown_device_kind():
    assert adaptive.vmem_bytes("TPU v5 lite") == 128 << 20
    with pytest.raises(ValueError, match="no VMEM size recorded"):
        adaptive.vmem_bytes("TPU v99")
