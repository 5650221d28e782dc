"""Streaming contract tests: ``GroupByPlan.stream`` ≡ one-shot across the
strategy × distribution matrix, idempotent mid-stream snapshots, in-stream
grow recovery, zero chunk retention on every streaming strategy, and
mid-stream ``auto`` re-planning."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import groupby_oracle
from repro.data.pipeline import ArraySource, ChunkSource, IterableSource
from repro.engine import (
    AggSpec,
    ExecutionPolicy,
    GroupByPlan,
    SaturationPolicy,
    Scan,
    Table,
)

RNG = np.random.default_rng(11)
N = 4096
CHUNK = 512  # 8-chunk streams everywhere

STREAMING = ("concurrent", "partitioned", "hybrid", "pallas")


def gen_keys(dist: str) -> np.ndarray:
    if dist == "uniform":
        return RNG.integers(0, 300, size=N).astype(np.uint32)
    if dist == "zipf":
        return (RNG.zipf(1.3, size=N) % (N // 2)).astype(np.uint32)
    assert dist == "unique"
    return RNG.permutation(N).astype(np.uint32)


def chunk_tables(keys, vals=None, chunk=CHUNK):
    for i in range(0, len(keys), chunk):
        cols = {"k": jnp.asarray(keys[i:i + chunk])}
        if vals is not None:
            cols["v"] = jnp.asarray(vals[i:i + chunk])
        yield Table(cols)


def table_map(out: Table, name: str) -> dict:
    n = int(out["__num_groups__"][0])
    return {int(k): float(v)
            for k, v in zip(np.asarray(out["key"])[:n], np.asarray(out[name])[:n])}


def oracle_map(keys, vals, kind="sum", max_groups=N):
    ref = groupby_oracle(jnp.asarray(keys), None if vals is None else jnp.asarray(vals),
                         kind=kind, max_groups=max_groups)
    n = int(ref.num_groups)
    return {int(k): float(v)
            for k, v in zip(np.asarray(ref.keys)[:n], np.asarray(ref.values)[:n])}


# ---------------------------------------------------------------------------
# stream ≡ one-shot equivalence matrix


@pytest.mark.parametrize("dist", ["uniform", "zipf", "unique"])
@pytest.mark.parametrize("strategy", STREAMING)
def test_stream_equals_oneshot_matrix(strategy, dist):
    """An 8-chunk stream and the one-shot run of the concatenated table
    produce the same groups: COUNT bit-exact on every strategy; SUM
    bit-exact on the carry-threading strategies (stream chunking preserves
    the per-ticket accumulation order) and fp-associativity-close on the
    chunk-partial-merge strategies."""
    keys = gen_keys(dist)
    vals = RNG.normal(size=N).astype(np.float32)
    plan = GroupByPlan(
        keys=("k",), aggs=(AggSpec("count"), AggSpec("sum", "v")),
        strategy=strategy, max_groups=N, saturation=SaturationPolicy.RAISE,
        raw_keys=True, execution=ExecutionPolicy(morsel_rows=256),
    )
    if strategy in ("partitioned", "sharded"):
        plan = plan.with_(aggs=(AggSpec("count"),))
    handle = plan.stream(chunk_tables(keys, vals))
    streamed = handle.result()
    oneshot = plan.run(Table({"k": jnp.asarray(keys), "v": jnp.asarray(vals)}))

    assert handle.peak_buffered_chunks == 0
    assert handle.chunks_consumed == N // CHUNK
    # COUNT: integers in f32 — bit-exact regardless of chunking
    assert table_map(streamed, "count(*)") == table_map(oneshot, "count(*)")
    assert table_map(streamed, "count(*)") == oracle_map(keys, None, kind="count")
    if strategy == "concurrent":
        # carry-threading: identical per-ticket accumulation order →
        # bit-exact sums regardless of chunk boundaries
        np.testing.assert_array_equal(
            np.asarray(streamed["sum(v)"]), np.asarray(oneshot["sum(v)"])
        )
    elif strategy in ("hybrid", "pallas"):
        # hybrid's heavy-candidate sample and pallas's chunk-partial merge
        # reorder fp adds — equal up to associativity
        got, want = table_map(streamed, "sum(v)"), table_map(oneshot, "sum(v)")
        assert got.keys() == want.keys()
        for k in want:
            assert abs(got[k] - want[k]) < 5e-2, (k, got[k], want[k])


# ---------------------------------------------------------------------------
# mid-stream snapshot semantics


def test_snapshot_is_idempotent_and_stream_continues():
    keys = gen_keys("uniform")
    vals = RNG.normal(size=N).astype(np.float32)
    plan = GroupByPlan(
        keys=("k",), aggs=(AggSpec("sum", "v"),), strategy="concurrent",
        max_groups=512, raw_keys=True, execution=ExecutionPolicy(morsel_rows=128),
    )
    handle = plan.stream(chunk_tables(keys, vals))
    assert handle.pump(4) == 4
    snap1 = handle.snapshot()
    snap2 = handle.snapshot()  # no pumping in between → identical
    for col in snap1.columns:
        np.testing.assert_array_equal(np.asarray(snap1[col]), np.asarray(snap2[col]))
    # snapshot reflects exactly the first 4 chunks
    assert table_map(snap1, "sum(v)") == pytest.approx(
        oracle_map(keys[: 4 * CHUNK], vals[: 4 * CHUNK]), abs=1e-3
    )
    # the stream continues past the snapshot to the full result
    final = handle.result()
    assert table_map(final, "sum(v)") == pytest.approx(oracle_map(keys, vals), abs=1e-3)
    assert handle.closed
    assert final is handle.result()  # terminal result is idempotent
    with pytest.raises(ValueError):
        handle.pump(1)


@pytest.mark.parametrize("strategy", ["partitioned", "pallas", "hybrid"])
def test_snapshot_midstream_other_strategies(strategy):
    keys = gen_keys("uniform")
    plan = GroupByPlan(
        keys=("k",), aggs=(AggSpec("count"),), strategy=strategy,
        max_groups=512, raw_keys=True,
    )
    handle = plan.stream(chunk_tables(keys))
    handle.pump(4)
    snap = table_map(handle.snapshot(), "count(*)")
    assert snap == oracle_map(keys[: 4 * CHUNK], None, kind="count")
    final = table_map(handle.result(), "count(*)")
    assert final == oracle_map(keys, None, kind="count")


# ---------------------------------------------------------------------------
# grow-under-streaming: a misestimated bound recovers with NO retained chunks


@pytest.mark.parametrize("strategy", STREAMING)
def test_grow_under_streaming_recovers(strategy):
    keys = RNG.integers(0, 1000, size=N).astype(np.uint32)
    vals = RNG.normal(size=N).astype(np.float32)
    aggs = (AggSpec("count"),) if strategy == "partitioned" else (AggSpec("sum", "v"),)
    plan = GroupByPlan(
        keys=("k",), aggs=aggs, strategy=strategy, max_groups=32,
        saturation=SaturationPolicy.GROW, raw_keys=True,
        execution=ExecutionPolicy(morsel_rows=128),
    )
    handle = plan.stream(chunk_tables(keys, vals))
    out = handle.result()
    assert handle.peak_buffered_chunks == 0  # grow never replays the stream
    name = aggs[0].name
    kind = aggs[0].kind
    assert table_map(out, name) == pytest.approx(
        oracle_map(keys, None if kind == "count" else vals, kind=kind,
                   max_groups=2048),
        abs=1e-2,
    )


@pytest.mark.parametrize("tail", [0, 100])
def test_partitioned_stream_bound_above_chunk(tail):
    """A bound larger than a chunk plus its pre-aggregation entries (every
    chunk here, and a short last one when ``tail``): the chunk pipeline's
    keys and values still line up with the carried table."""
    keys = RNG.permutation(N + tail).astype(np.uint32)
    plan = GroupByPlan(
        keys=("k",), aggs=(AggSpec("count"),), strategy="partitioned",
        max_groups=4 * N, raw_keys=True,
    )
    out = plan.stream(chunk_tables(keys)).result()
    assert table_map(out, "count(*)") == oracle_map(
        keys, None, kind="count", max_groups=N + tail
    )


def test_grow_streaming_with_deep_prefetch_matches_sync():
    """Deferred polls (prefetch window > 0) must not change results even
    when pauses fire while several chunks are in flight."""
    keys = RNG.integers(0, 2000, size=N).astype(np.uint32)
    plan = GroupByPlan(
        keys=("k",), aggs=(AggSpec("count"),), strategy="concurrent",
        max_groups=16, saturation=SaturationPolicy.GROW, raw_keys=True,
        execution=ExecutionPolicy(morsel_rows=64),
    )
    outs = {}
    for pf in (0, 2, 6):
        outs[pf] = table_map(
            plan.stream(chunk_tables(keys), prefetch=pf).result(), "count(*)"
        )
    assert outs[0] == outs[2] == outs[6]
    assert outs[0] == oracle_map(keys, None, kind="count")


# ---------------------------------------------------------------------------
# who buffers: streaming strategies retain nothing; one-shots are documented


def test_peak_buffered_chunks_zero_for_streaming_strategies():
    keys = gen_keys("uniform")
    for strategy in STREAMING:
        plan = GroupByPlan(
            keys=("k",), aggs=(AggSpec("count"),), strategy=strategy,
            max_groups=512, raw_keys=True,
        )
        handle = plan.stream(chunk_tables(keys))
        handle.result()
        assert handle.peak_buffered_chunks == 0, strategy
        assert handle.chunks_consumed == 8


def test_sort_ticketing_is_oneshot_and_buffers():
    keys = gen_keys("uniform")
    plan = GroupByPlan(
        keys=("k",), aggs=(AggSpec("count"),), strategy="concurrent",
        max_groups=512, raw_keys=True,
        execution=ExecutionPolicy(ticketing="sort", update="sort_segment"),
    )
    handle = plan.stream(chunk_tables(keys))
    out = handle.result()
    assert handle.peak_buffered_chunks == 8  # documented pipeline breaker
    assert table_map(out, "count(*)") == oracle_map(keys, None, kind="count")


# ---------------------------------------------------------------------------
# direct ticketing streams (ticket == key over a bounded domain)


def test_direct_ticketing_streams_without_buffering():
    """Direct ticketing consumes chunk-by-chunk with NO retained chunks:
    tickets are stable across the whole stream (ticket == key), so the
    accumulator carries and every chunk is dropped after its scatter."""
    keys = np.concatenate(
        [np.arange(300, dtype=np.uint32),
         RNG.integers(0, 300, size=N - 300).astype(np.uint32)]
    )
    RNG.shuffle(keys)
    vals = RNG.normal(size=N).astype(np.float32)
    plan = GroupByPlan(
        keys=("k",), aggs=(AggSpec("count"), AggSpec("sum", "v")),
        strategy="concurrent", max_groups=512,
        saturation=SaturationPolicy.RAISE, raw_keys=True,
        execution=ExecutionPolicy(ticketing="direct", key_domain=300),
    )
    handle = plan.stream(chunk_tables(keys, vals))
    out = handle.result()
    assert handle.peak_buffered_chunks == 0  # was 8 before the refactor
    assert handle.chunks_consumed == 8
    assert table_map(out, "count(*)") == oracle_map(keys, None, kind="count")
    assert table_map(out, "sum(v)") == pytest.approx(
        oracle_map(keys, vals), abs=1e-3
    )


def test_direct_ticketing_grows_domain_midstream():
    """Keys past the planned domain arrive only in later chunks; GROW
    widens the domain and the accumulators in-stream without replay."""
    early = RNG.integers(0, 64, size=N // 2).astype(np.uint32)
    late = RNG.integers(0, 500, size=N // 2).astype(np.uint32)
    keys = np.concatenate([early, late])
    plan = GroupByPlan(
        keys=("k",), aggs=(AggSpec("count"),), strategy="concurrent",
        max_groups=64, saturation=SaturationPolicy.GROW, raw_keys=True,
        execution=ExecutionPolicy(ticketing="direct"),
    )
    handle = plan.stream(chunk_tables(keys))
    out = handle.result()
    assert handle.peak_buffered_chunks == 0
    n = int(out["__num_groups__"][0])
    want = np.bincount(keys, minlength=n).astype(np.float32)
    np.testing.assert_array_equal(np.asarray(out["count(*)"])[:n], want[:n])
    np.testing.assert_array_equal(np.asarray(out["key"])[:n], np.arange(n))


def test_direct_ticketing_raise_on_stream_overflow():
    keys = RNG.integers(0, 500, size=N).astype(np.uint32)
    plan = GroupByPlan(
        keys=("k",), aggs=(AggSpec("count"),), strategy="concurrent",
        max_groups=64, saturation=SaturationPolicy.RAISE, raw_keys=True,
        execution=ExecutionPolicy(ticketing="direct"),
    )
    from repro.engine import GroupByOverflowError

    with pytest.raises(GroupByOverflowError, match="direct-ticketing overflow"):
        plan.collect(chunk_tables(keys))


# ---------------------------------------------------------------------------
# sharded streams: full AggState carries → multi-aggregate / mean


@pytest.mark.parametrize("merge", ["dense_psum", "all_to_all"])
def test_sharded_stream_multi_aggregate(merge):
    """The sharded carry holds a full AggState pytree, so a sharded stream
    accepts multiple aggregates (incl. composed mean) like every other
    strategy — previously it was limited to one accumulator."""
    import jax

    keys = gen_keys("uniform")
    vals = RNG.normal(size=N).astype(np.float32)
    mesh = jax.make_mesh((1,), ("data",))
    plan = GroupByPlan(
        keys=("k",),
        aggs=(AggSpec("sum", "v"), AggSpec("mean", "v"),
              AggSpec("count"), AggSpec("min", "v")),
        strategy="sharded", max_groups=512,
        saturation=SaturationPolicy.UNCHECKED, raw_keys=True,
        execution=ExecutionPolicy(mesh=mesh, axis="data", shard_merge=merge),
    )
    handle = plan.stream(chunk_tables(keys, vals))
    out = handle.result()
    assert handle.peak_buffered_chunks == 0
    sums = oracle_map(keys, vals, kind="sum")
    counts = oracle_map(keys, None, kind="count")
    assert table_map(out, "count(*)") == counts
    assert table_map(out, "sum(v)") == pytest.approx(sums, abs=1e-3)
    assert table_map(out, "min(v)") == pytest.approx(
        oracle_map(keys, vals, kind="min"), abs=1e-5
    )
    assert table_map(out, "mean(v)") == pytest.approx(
        {k: sums[k] / counts[k] for k in sums}, abs=1e-4
    )


def test_sharded_stream_multi_aggregate_grow():
    import jax

    keys = RNG.integers(0, 700, size=N).astype(np.uint32)
    vals = RNG.normal(size=N).astype(np.float32)
    mesh = jax.make_mesh((1,), ("data",))
    plan = GroupByPlan(
        keys=("k",), aggs=(AggSpec("mean", "v"), AggSpec("count")),
        strategy="sharded", max_groups=64,
        saturation=SaturationPolicy.GROW, raw_keys=True,
        execution=ExecutionPolicy(mesh=mesh, axis="data"),
    )
    out = plan.collect(chunk_tables(keys, vals))
    sums = oracle_map(keys, vals, kind="sum")
    counts = oracle_map(keys, None, kind="count")
    assert table_map(out, "count(*)") == counts
    assert table_map(out, "mean(v)") == pytest.approx(
        {k: sums[k] / counts[k] for k in sums}, abs=1e-4
    )


def test_sharded_psum_merge_counts_union_past_bound():
    """Under a global bound below the union, the dense-psum merge still
    counts the union exactly and flags the overflow, so GROW re-merges once
    at the true size (a probe table sized to the bound alone saturates,
    caps the count at its capacity and spins every unplaced lane)."""
    import jax

    from repro.core import distributed as dist

    mesh = jax.make_mesh((1,), ("data",))
    carry = dist.make_sharded_carry(1, 1024, ((None, "count"),))
    carry = carry._replace(
        kbt=carry.kbt.at[0, :700].set(jnp.arange(1, 701, dtype=jnp.uint32)),
        count=jnp.full((1,), 700, jnp.int32),
    )
    _, _, count, lost, union_ovf = dist.sharded_psum_merge(
        mesh, "data", carry, max_groups=64
    )
    assert (int(count), int(lost), int(union_ovf)) == (700, 0, 1)


# ---------------------------------------------------------------------------
# ChunkSource adapters


def test_chunk_source_adapters_agree():
    keys = gen_keys("uniform")
    vals = RNG.normal(size=N).astype(np.float32)
    table = Table({"k": jnp.asarray(keys), "v": jnp.asarray(vals)})
    plan = GroupByPlan(
        keys=("k",), aggs=(AggSpec("sum", "v"),), strategy="concurrent",
        max_groups=512, raw_keys=True,
    )
    sources = {
        "table": table,
        "scan": Scan(table, chunk_rows=CHUNK),
        "array": ArraySource({"k": jnp.asarray(keys), "v": jnp.asarray(vals)},
                             chunk_rows=CHUNK),
        "iterable": IterableSource(list(chunk_tables(keys, vals))),
        "generator": chunk_tables(keys, vals),
    }
    assert isinstance(sources["scan"], ChunkSource)
    assert isinstance(sources["array"], ChunkSource)
    want = oracle_map(keys, vals)
    for name, src in sources.items():
        got = table_map(plan.collect(src), "sum(v)")
        assert got == pytest.approx(want, abs=1e-3), name


def test_bad_chunk_source_raises():
    plan = GroupByPlan(keys=("k",), aggs=(AggSpec("count"),), max_groups=8,
                       strategy="concurrent", raw_keys=True)
    with pytest.raises(TypeError):
        plan.stream(42)


def test_synthetic_lm_is_a_chunk_source():
    from repro.data.pipeline import SyntheticLM
    from repro.models.config import ModelConfig

    cfg = ModelConfig(name="tiny", family="dense", vocab_size=512, d_model=16,
                      n_layers=1, n_heads=2, d_ff=32)
    lm = SyntheticLM(cfg, batch=4, seq=32, track_stats=False, seed=3)
    assert isinstance(lm, ChunkSource)
    plan = GroupByPlan(
        keys=("token",), aggs=(AggSpec("count"),), strategy="concurrent",
        max_groups=4096, saturation=SaturationPolicy.UNCHECKED, raw_keys=True,
    )
    handle = plan.stream(lm)  # unbounded source: pump a bounded number
    assert handle.pump(3) == 3
    snap = handle.snapshot()
    n = int(snap["__num_groups__"][0])
    counts = np.asarray(snap["count(*)"])[:n]
    # 3 batches × 4 rows × 32 tokens, minus the masked-out tail of the
    # tracked key space (keys ≥ stat_groups//2 become the EMPTY sentinel)
    assert 0 < counts.sum() <= 3 * 4 * 32


# ---------------------------------------------------------------------------
# auto re-planning mid-stream


def test_auto_replans_hash_to_hybrid_midstream():
    """A stream whose heavy-hitter mass only emerges after the first chunk:
    the resolver picks hash-concurrent from chunk 1, the running stats
    cross the planner threshold later, and the executor escalates to
    hybrid by ADOPTING the live operator — the final counts stay exact."""
    from repro.engine.executors import _HybridExecutor, _ScanExecutor

    rng = np.random.default_rng(23)
    n_chunk, n_chunks = 8192, 6
    chunks, parts = [], []
    for i in range(n_chunks):
        k = rng.integers(0, 20000, size=n_chunk).astype(np.uint32)
        if i >= 2:
            k[rng.random(n_chunk) < 0.5] = 7
        parts.append(k)
        chunks.append(Table({"k": jnp.asarray(k)}))
    plan = GroupByPlan(keys=("k",), aggs=(AggSpec("count"),), strategy="auto",
                       raw_keys=True)
    handle = plan.stream(iter(chunks))
    handle.pump(2)
    resolver = handle._ex
    assert isinstance(resolver._inner, _ScanExecutor)
    out = handle.result()
    assert isinstance(resolver._inner, _HybridExecutor)
    assert resolver._escalated

    keys = np.concatenate(parts)
    want = {int(k): float(c) for k, c in zip(*np.unique(keys, return_counts=True))}
    assert table_map(out, "count(*)") == want
