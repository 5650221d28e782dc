"""The planner's per-chunk key sample (``_ResolvingExecutor``): one compiled
launch per chunk whose keys equal ``chunk_key_column`` on the chunk's head
bit for bit, read blocking only for the first chunk and folded into the
``RunningStats`` sketch one chunk later.  The sketch after a drained stream
equals the synchronous fold's field for field, an escalation comes at most
one chunk later, and a checkpoint exports the pending sample folded."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import adaptive
from repro.engine import AggSpec, ExecutionPolicy, GroupByPlan, Table
from repro.engine.columns import chunk_key_column
from repro.engine.executors import _HybridExecutor, _ResolvingExecutor

SAMPLE = _ResolvingExecutor.SAMPLE_ROWS


def table_map(out: Table, name: str = "count(*)") -> dict:
    n = int(out["__num_groups__"][0])
    return {int(k): float(v)
            for k, v in zip(np.asarray(out["key"])[:n], np.asarray(out[name])[:n])}


def heavy_late_chunks():
    """Six 8,192-row chunks of uniform keys in [0, 20000); from chunk 2 on,
    half the rows are key 7 (the replanning stream of test_stream)."""
    rng = np.random.default_rng(23)
    chunks = []
    for i in range(6):
        k = rng.integers(0, 20000, size=8192).astype(np.uint32)
        if i >= 2:
            k[rng.random(8192) < 0.5] = 7
        chunks.append(Table({"k": jnp.asarray(k)}))
    return chunks


def unique_chunks():
    """Six 8,192-row chunks of distinct keys: the distinct set saturates."""
    keys = np.random.default_rng(5).permutation(6 * 8192).astype(np.uint32)
    return [Table({"k": jnp.asarray(keys[i:i + 8192])})
            for i in range(0, keys.size, 8192)]


def few_key_chunks():
    """Six 8,192-row chunks of 8 keys beside a value column: with small
    morsels the carry stays smaller than a chunk, so each sample is launched
    before its scan."""
    rng = np.random.default_rng(7)
    return [Table({"k": jnp.asarray(rng.integers(0, 8, 8192).astype(np.uint32)),
                   "v": jnp.asarray(rng.random(8192).astype(np.float32))})
            for _ in range(6)]


def raw_plan(**execution):
    return GroupByPlan(keys=("k",), aggs=(AggSpec("count"),), strategy="auto",
                       raw_keys=True, execution=ExecutionPolicy(**execution))


def head(chunk: Table) -> Table:
    return Table({c: v[:SAMPLE] for c, v in chunk.columns.items()})


def sketch(s: adaptive.RunningStats) -> dict:
    return {"counters": s._counters, "distinct": s._distinct,
            "n_rows": s.n_rows, "sampled": s.sampled,
            "saturated": s._distinct_saturated}


def synchronous_sketch(plan, chunks, distinct_cap=1 << 16):
    ref = adaptive.RunningStats(distinct_cap=distinct_cap)
    for c in chunks:
        ref.update(chunk_key_column(head(c), plan.keys, plan.raw_keys)[0])
    return ref


def byte_keys_masked():
    rng = np.random.default_rng(1)
    n = 3 * SAMPLE
    return (Table({"a": jnp.asarray(rng.integers(0, 256, n).astype(np.uint8)),
                   "b": jnp.asarray(rng.integers(0, 256, n).astype(np.uint8)),
                   "v": jnp.asarray(rng.random(n).astype(np.float32)),
                   "__mask__": jnp.asarray(rng.random(n) < 0.7)}),
            ("a", "b"), False)


def raw_uint32_key():
    keys = np.random.default_rng(2).integers(0, 1 << 32, 2 * SAMPLE, dtype=np.uint64)
    keys = np.minimum(keys, 0xFFFFFFFE).astype(np.uint32)
    return Table({"k": jnp.asarray(keys)}), ("k",), True


def short_chunk():
    rng = np.random.default_rng(3)
    n = SAMPLE // 4 + 3
    return (Table({"a": jnp.asarray(rng.integers(0, 9, n).astype(np.int32)),
                   "__mask__": jnp.asarray(rng.random(n) < 0.5)}),
            ("a",), False)


@pytest.mark.parametrize("make", [byte_keys_masked, raw_uint32_key, short_chunk])
def test_compiled_head_keys_match_chunk_key_column(make):
    chunk, keys, raw = make()
    plan = GroupByPlan(keys=keys, aggs=(AggSpec("count"),), raw_keys=raw)
    got = np.asarray(_ResolvingExecutor(plan)._sample_keys(chunk))
    want = np.asarray(chunk_key_column(head(chunk), keys, raw)[0])
    assert got.dtype == want.dtype == np.uint32
    assert got.shape == (min(SAMPLE, chunk.num_rows),)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("chunks,distinct_cap,morsel_rows,ahead", [
    (heavy_late_chunks, 1 << 16, 4096, False),
    (unique_chunks, 2 * SAMPLE, 4096, False),
    (few_key_chunks, 1 << 16, 256, True),
])
def test_deferred_sketch_equals_synchronous(chunks, distinct_cap, morsel_rows, ahead):
    """Launched behind its scan (carry larger than the chunk) or ahead of
    it, the deferred fold gives the synchronous sketch."""
    chunks, plan = chunks(), raw_plan(morsel_rows=morsel_rows)
    handle = plan.stream(iter(chunks))
    resolver = handle._ex
    resolver._stats.distinct_cap = distinct_cap
    handle.result()
    chunk_bytes = sum(int(v.nbytes) for v in chunks[-1].columns.values())
    assert (resolver._inner.device_table_bytes() <= chunk_bytes) == ahead
    assert resolver._pending is not None  # finalize leaves the last unfolded
    resolver.settle_sample()
    want = sketch(synchronous_sketch(plan, chunks, distinct_cap))
    assert sketch(resolver._stats) == want
    assert want["saturated"] == (distinct_cap < 1 << 16)


def test_escalation_at_most_one_chunk_later():
    """The synchronous order escalates once chunk 4's sample is folded; the
    deferred fold of chunk 4 comes at chunk 5, still inside the stream."""
    chunks, plan = heavy_late_chunks(), raw_plan()
    ref, sync_at = adaptive.RunningStats(), None
    for i, c in enumerate(chunks):
        st = ref.update(chunk_key_column(head(c), plan.keys, True)[0])
        if i and sync_at is None and st.est_top_freq >= 0.25 and st.est_groups > 4096:
            sync_at = i

    handle = plan.stream(iter(chunks))
    resolver, deferred_at = handle._ex, None
    for i in range(len(chunks)):
        handle.pump(1)
        if deferred_at is None and resolver._escalated:
            deferred_at = i
    out = handle.result()
    assert (sync_at, deferred_at) == (4, 5)
    assert isinstance(resolver._inner, _HybridExecutor)
    keys = np.concatenate([np.asarray(c["k"]) for c in chunks])
    assert table_map(out) == {int(k): float(n)
                              for k, n in zip(*np.unique(keys, return_counts=True))}


def test_planner_counters():
    chunks = heavy_late_chunks()
    handle = raw_plan().stream(iter(chunks))
    handle.result()
    planner = handle.stats()["planner"]
    assert planner["samples"] == len(chunks)
    assert planner["blocking_reads"] == 1
    # chunk 1's sample is folded at chunk 2, ..., the last one never
    assert planner["deferred_folds"] == len(chunks) - 2
    assert 0 <= planner["folds_waited"] <= planner["deferred_folds"]


@pytest.mark.parametrize("save_at", [2, 5])
def test_checkpoint_exports_folded_sketch(save_at, tmp_path):
    """``save`` with a sample pending folds it (at 5 that fold escalates):
    the restored stream resumes from the full sketch, and finishing it gives
    the uninterrupted run's result and sketch."""
    chunks, plan = heavy_late_chunks(), raw_plan()
    straight = plan.stream(iter(chunks))
    want = table_map(straight.result())
    straight._ex.settle_sample()

    h = plan.stream(iter(chunks))
    h.pump(save_at)
    assert h._ex._pending is not None
    h.save(str(tmp_path))
    assert h._ex._pending is None
    saved = sketch(h._ex._stats)
    assert h._ex._escalated == (save_at == 5)

    h2 = plan.restore(str(tmp_path), iter(chunks))
    assert sketch(h2._ex._stats) == saved
    assert h2._ex._escalated == h._ex._escalated
    assert table_map(h2.result()) == table_map(h.result()) == want
    for r in (h._ex, h2._ex):
        r.settle_sample()
        assert sketch(r._stats) == sketch(straight._ex._stats)
        assert r._escalated and straight._ex._escalated
