"""Executors: the single seam every GROUP BY strategy lowers through.

``make_executor(plan)`` turns a declarative :class:`GroupByPlan` into an
object implementing the morsel-driven STREAMING operator protocol

    open() → consume(chunk: Table)* → finalize() → Table

plus the pull-based extensions the plan API's :class:`StreamHandle` drives:

  * ``consume_async(chunk) → token`` / ``poll(token)`` — the double-buffered
    ingest seam: ``consume_async`` dispatches the chunk's device work and
    returns immediately, so the host stages (pulls + morselizes) the next
    chunk while the device scan is in flight; ``poll`` later resolves the
    chunk's control signals (pause flags, overflow) in dispatch order.
    ``consume`` ≡ ``poll(consume_async(chunk))``.
  * ``finalize`` is an idempotent read on every strategy — a mid-stream
    ``snapshot()`` materializes the groups seen so far and consumption can
    continue afterwards.

The strategies:

  * ``concurrent`` — the scan-compiled morsel pipeline (hash ticketing);
    streams natively, retains no chunks.  ``saturation="grow"`` rides the
    operator's in-stream pause→widen→resume bound growth (no replay).
    ``execution.ticketing="direct"`` swaps in the perfect-hash variant
    (ticket == key over a bounded domain — tickets are stable across
    chunks, so it streams chunk-by-chunk with a carried accumulator);
    ``ticketing="sort"`` is the one genuinely ONE-SHOT executor left
    (sorting is a pipeline breaker over the full input), documented as such.
  * ``hybrid``     — heavy-hitter register path + concurrent tail; streams
    (registers fold per chunk, the tail rides the scan pipeline).
  * ``pallas``     — kernel-backed ticket→update per chunk, merged into a
    carried ticket table (state O(max_groups), no buffered chunks).
  * ``partitioned``— per-chunk Leis-style preagg/exchange/final, the chunk
    partial merged into a carried table at consume (incremental).
  * ``sharded``    — mesh execution with per-device state carried across
    chunks (``core.distributed.ShardedCarry``) and ONE merge at finalize:
    state is O(devices × capacity), independent of the stream length.

Saturation is enforced here, uniformly: every executor implements
``raise`` / ``grow`` / ``unchecked`` (plan_api.SaturationPolicy).  ``grow``
no longer replays retained chunks — the streaming executors either widen
their bound in-stream BEFORE anything is dropped (concurrent, hybrid,
sharded: §4.4 pause/migrate/resume applied to the cardinality bound) or
recover per chunk and grow their carried merge state (pallas, partitioned,
direct).  Only the one-shot sort executor still gathers the stream.
``saturation="spill"`` lowers to the out-of-core executor
(``engine/spill.py``): the concurrent hash pipeline with a bounded device
residency and host-spilled cold partitions, merged exactly at finalize.
"""
from __future__ import annotations

import functools
import warnings
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import adaptive, resize
from repro.core import ticketing as tk
from repro.core import updates as up
from repro.core.hashing import EMPTY_KEY, table_capacity
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.engine.columns import Table, chunk_key_column, combine_keys
from repro.engine.groupby import (
    GroupByOperator,
    GroupByOverflowError,
    build_result_table,
    expand_agg_specs,
)
from repro.engine.plan_api import (
    GroupByPlan,
    SaturationPolicy,
    value_columns,
)


# ---------------------------------------------------------------------------
# kernel-selector normalization: ExecutionPolicy.kernel is THE selector; the
# legacy spellings lower onto it here, warning once per process per alias.

_ALIAS_WARNED: set = set()


def _warn_alias_once(alias: str, repl: str) -> None:
    if alias in _ALIAS_WARNED:
        return
    _ALIAS_WARNED.add(alias)
    warnings.warn(
        f"{alias} is deprecated; use ExecutionPolicy.kernel={repl!r}",
        DeprecationWarning,
        stacklevel=4,
    )


def reset_kernel_alias_warnings() -> None:
    """Re-arm the once-per-process alias warnings (test helper)."""
    _ALIAS_WARNED.clear()


def normalize_kernel(plan: GroupByPlan) -> GroupByPlan:
    """Lower the deprecated kernel spellings onto ``ExecutionPolicy.kernel``:
    ``strategy="pallas"`` → ``concurrent`` + ``kernel="split"`` and
    ``use_kernel=True`` → ``kernel="scan_body"`` (an explicit ``kernel``
    wins over either alias).  Idempotent — normalized plans pass through
    untouched, so re-entrant dispatch (auto resolution) never double-warns."""
    ex = plan.execution
    strategy, kernel, changed = plan.strategy, ex.kernel, False
    if strategy == "pallas":
        _warn_alias_once('strategy="pallas"', "split")
        strategy = "concurrent"
        kernel = kernel or "split"
        changed = True
    if ex.use_kernel:
        _warn_alias_once("ExecutionPolicy.use_kernel", "scan_body")
        kernel = kernel or "scan_body"
        changed = True
    if changed:
        plan = replace(
            plan, strategy=strategy,
            execution=replace(ex, kernel=kernel, use_kernel=False),
        )
    return plan


def make_executor(plan: GroupByPlan):
    """Lower a plan to its executor.  ``strategy="auto"`` (or an unset
    ``max_groups``) defers to a resolving wrapper that samples the first
    chunk's keys and re-dispatches — the paper's estimate → choose → run —
    and keeps running statistics across the stream for mid-stream
    re-planning."""
    plan = normalize_kernel(plan)
    _refuse_uncompilable_kernel(plan)
    kernel = plan.execution.kernel
    if kernel in ("split", "fused"):
        if plan.strategy not in ("auto", "concurrent"):
            raise ValueError(
                f"kernel={kernel!r} runs on the concurrent hash pipeline; "
                f"strategy {plan.strategy!r} does not support it"
            )
        if plan.execution.ticketing != "hash":
            raise ValueError(f"kernel={kernel!r} requires ticketing='hash'")
        if plan.saturation == SaturationPolicy.SPILL:
            raise ValueError(
                "saturation='spill' runs on the scan pipeline; use "
                "kernel=None/'off'/'scan_body'"
            )
    if plan.saturation is None:
        # THE saturation default: an estimated bound recovers (a sample
        # cannot see a long tail); an explicit bound is a caller contract.
        plan = replace(plan, saturation=(
            SaturationPolicy.GROW if plan.max_groups is None
            else SaturationPolicy.RAISE
        ))
    if plan.saturation == SaturationPolicy.SPILL:
        if plan.strategy not in ("auto", "concurrent"):
            raise ValueError(
                "saturation='spill' runs on the concurrent hash pipeline; "
                f"strategy {plan.strategy!r} does not support spilling"
            )
        if plan.strategy == "concurrent" and plan.execution.ticketing != "hash":
            raise ValueError(
                "saturation='spill' requires ticketing='hash' (the hot "
                "table is the probe table the spill router classifies "
                "against)"
            )
        if plan.strategy == "concurrent" and plan.max_groups is not None:
            from repro.engine.spill import SpillExecutor

            return SpillExecutor(plan)
    if plan.strategy == "auto" or plan.max_groups is None:
        return _ResolvingExecutor(plan)
    if plan.strategy == "concurrent":
        if plan.execution.ticketing == "sort":
            return _SortExecutor(plan)
        if plan.execution.ticketing == "direct":
            return _DirectExecutor(plan)
        if kernel == "split":
            return _PallasExecutor(plan)
        if kernel == "fused":
            return _FusedExecutor(plan)
        return _ScanExecutor(plan)
    if plan.strategy == "hybrid":
        return _HybridExecutor(plan)
    if plan.strategy == "partitioned":
        return _PartitionedExecutor(plan)
    if plan.strategy == "sharded":
        return _ShardedExecutor(plan)
    raise ValueError(f"unknown strategy {plan.strategy!r}")


# ---------------------------------------------------------------------------
# shared helpers


def _refuse_uncompilable_kernel(plan: GroupByPlan) -> None:
    """An explicit kernel route that this backend's compiler refuses raises
    here, quoting the compiler — never a quiet switch to another route."""
    from repro.kernels.ops import backend_refusal

    kernel, update = plan.execution.kernel, plan.execution.update
    if kernel == "scan_body" and update is None and (
        plan.strategy == "auto" or plan.max_groups is None
    ):
        return  # the planner picks the update; the resolved plan is checked
    refusal = backend_refusal(kernel, update or "scatter")
    if refusal is not None:
        raise NotImplementedError(
            f"kernel={kernel!r} (update={update!r}) does not compile for "
            f"{jax.default_backend()}: the compiler raises {refusal!r}"
        )


def _instrument(plan: GroupByPlan) -> bool:
    """Resolve the per-plan instrumentation flag: an explicit
    ``ExecutionPolicy.instrument`` wins; ``None`` follows the global
    ``obs.metrics`` enable flag (so ``metrics.enable()`` turns on in-scan
    event collection for every plan built afterwards)."""
    ins = plan.execution.instrument
    return obs_metrics.enabled() if ins is None else bool(ins)


class _ExecutorBase:
    """Default streaming protocol: executors without their own async seam
    consume synchronously (``consume_async`` degenerates), and executors
    that retain no chunks report a zero buffer high-water mark."""

    peak_buffered_chunks = 0  # chunks retained beyond the in-flight window
    peak_retained_bytes = 0   # host bytes retained beyond the in-flight window
    strategy_label = "?"      # labeled-series key for registry publishing

    @property
    def plan(self) -> GroupByPlan:
        """The plan this executor runs (the resolved one, for ``auto``)."""
        return self._plan

    def open(self) -> None:
        pass

    def consume_async(self, chunk: Table):
        self.consume(chunk)
        return None

    def poll(self, token) -> None:
        pass

    def memory_stats(self) -> dict:
        """Uniform memory-telemetry read (``StreamHandle.stats()`` surfaces
        it): retention high-water marks, extended by executors that buffer
        (sort) or spill (engine/spill.py) with their own counters."""
        return {
            "peak_buffered_chunks": self.peak_buffered_chunks,
            "peak_retained_bytes": self.peak_retained_bytes,
        }

    # -- unified observability schema ---------------------------------------

    def device_table_bytes(self) -> int:
        """Current device footprint of the carried table/accumulator state
        (0 for executors with no carried device table)."""
        return 0

    def event_counts(self) -> dict | None:
        """Merged device+host event counters, or None when the executor is
        not instrumented (so ``stats()`` never forces a device sync on an
        uninstrumented stream)."""
        return None

    def stats(self) -> dict:
        """THE unified executor stats schema: the ``memory_stats()`` keys
        stay at the top level (compat view), plus nested ``memory`` /
        ``device`` sections; instrumented executors add their in-scan event
        counters under ``device`` and publish them (delta-based) into the
        ``obs.metrics`` registry."""
        mem = self.memory_stats()
        out = dict(mem)
        out["schema"] = "repro.obs/v1"
        out["strategy"] = self.strategy_label
        out["memory"] = {
            "peak_buffered_chunks": mem.get("peak_buffered_chunks", 0),
            "peak_retained_bytes": mem.get("peak_retained_bytes", 0),
        }
        dev = {"device_table_bytes": self.device_table_bytes()}
        ev = self.event_counts()
        if ev is not None:
            dev.update(ev)
            self.publish(ev)
        out["device"] = dev
        return out

    def publish(self, ev: dict | None = None) -> None:
        """Push the executor's counters into the process-wide registry as
        labeled series (``strategy=...``).  Delta-based, so idempotent
        surfaces (``stats``/``finalize``/``snapshot``) never double-count;
        a no-op while the registry is disabled."""
        if not obs_metrics.enabled():
            return
        if ev is None:
            ev = self.event_counts()
        if ev is None:
            return
        pub = getattr(self, "_obs_publisher", None)
        if pub is None:
            pub = obs_metrics.EventPublisher(strategy=self.strategy_label)
            self._obs_publisher = pub
        gauges = ("table_capacity", "table_load_factor", "num_groups")
        totals = {
            f"groupby.{k}": v for k, v in ev.items()
            if k not in gauges and isinstance(v, (int, float))
        }
        if "probe_hist" in ev:
            totals["groupby.probe_len"] = ev["probe_hist"]
        pub.publish(totals)
        for g in gauges:
            if g in ev:
                obs_metrics.gauge(
                    f"groupby.{g}", strategy=self.strategy_label
                ).set(ev[g])


def _chunk_keys_values(plan: GroupByPlan, chunk: Table):
    """Canonicalize one chunk: uint32 key column (combined or raw, with the
    ``__mask__`` selection vector applied) + float32 value columns."""
    keys, cols = chunk_key_column(chunk, plan.keys, plan.raw_keys)
    vals = {c: cols[c].reshape(-1).astype(jnp.float32) for c in value_columns(plan.aggs)}
    return keys, vals


def _concat(parts):
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts)


def _next_bound(max_groups: int, rows: int, issued: int | None = None) -> int:
    """THE grow rule.  With the true cardinality known (``issued``) jump
    straight to it; blind retries grow 4× (geometric → O(log) replays).
    ``rows`` always suffices, so the recovery loop terminates."""
    if issued is not None:
        return min(max(issued, 64), max(rows, issued))
    return min(max(4 * max_groups, 64), rows)


def _overflow_error(count, max_groups) -> GroupByOverflowError:
    return GroupByOverflowError(
        f"GROUP BY overflow: {count} distinct keys exceed "
        f"max_groups={max_groups}; groups past the bound were dropped. "
        "Use SaturationPolicy.GROW, a larger max_groups, or a better "
        "cardinality estimate."
    )


def _single_agg(plan: GroupByPlan, strategy: str):
    if len(plan.aggs) != 1 or plan.aggs[0].kind == "mean":
        raise ValueError(
            f"strategy {strategy!r} supports exactly one non-mean aggregate "
            "per plan; use strategy='concurrent' for multi-aggregate queries"
        )
    return plan.aggs[0]


_MERGE_KIND = {"count": "sum", "sum": "sum", "min": "min", "max": "max"}


# ---------------------------------------------------------------------------
# auto resolution (estimate → choose → run → re-plan)


def resolve_plan_stats(plan: GroupByPlan, stats: adaptive.WorkloadStats) -> GroupByPlan:
    """Bind ``strategy="auto"`` / ``max_groups=None`` from workload
    statistics (core/adaptive.py — the paper's Table 1 policy, plus the
    hybrid route for its worst corner: high cardinality under heavy
    hitters)."""
    max_groups = plan.max_groups
    if max_groups is None:
        # 2× headroom over the estimate, never above the row count, never 0.
        max_groups = max(1, min(max(stats.est_groups * 2, 64), max(stats.n_rows, 1)))
    strategy, execution = plan.strategy, plan.execution
    if strategy == "auto":
        if plan.saturation == SaturationPolicy.SPILL:
            # spill IS the concurrent hash pipeline plus a host cold path;
            # the resolved bound becomes its device residency budget
            strategy = "concurrent"
            update = execution.update or "scatter"
        elif stats.est_top_freq >= 0.25 and stats.est_groups > 4096:
            # Heavy hitters at high cardinality (paper Table 2's 0.34×–0.48×
            # corner): absorb the hitters in registers, run the tail clean.
            strategy = "hybrid"
            update = execution.update or "scatter"
        else:
            choice = adaptive.choose_plan(
                stats, num_accumulators=len(expand_agg_specs(plan.aggs))
            )
            strategy = "concurrent"
            update = execution.update or (
                "sort_segment" if choice.ticketing == "sort" else choice.update
            )
            if (choice.ticketing == "direct" and execution.ticketing == "hash"
                    and plan.raw_keys):
                # bounded key domain: perfect-hash ticketing, ticket == key
                execution = replace(
                    execution, ticketing="direct",
                    key_domain=execution.key_domain or stats.key_domain,
                )
            elif (choice.kernel == "fused" and execution.kernel is None
                    and execution.ticketing == "hash"):
                # estimated table + accumulators fit the VMEM budget: run
                # the single fused kernel instead of the scan pipeline
                execution = replace(execution, kernel="fused")
        execution = replace(execution, update=update)
    return replace(plan, strategy=strategy, max_groups=max_groups, execution=execution)


def resolve_plan(plan: GroupByPlan, keys: jnp.ndarray) -> GroupByPlan:
    """One-shot resolution from a key sample (kept for library callers; the
    streaming resolver below carries :class:`adaptive.RunningStats` across
    chunks instead of sampling once)."""
    # a caller-declared bounded key domain (e.g. expert ids) reaches the
    # planner's direct-ticketing rule through ExecutionPolicy.key_domain
    stats = adaptive.sample_stats(keys, domain=plan.execution.key_domain)
    return resolve_plan_stats(plan, stats)


class _ResolvingExecutor(_ExecutorBase):
    """Defers strategy/bound resolution to the first consumed chunk, then
    carries :class:`adaptive.RunningStats` across the stream and RE-PLANS
    mid-stream: a hash-ticketed concurrent pipeline escalates to hybrid
    when the observed heavy-hitter mass crosses the planner threshold (the
    operator — table, accumulators, grown bound — is adopted in place, so
    nothing replays).  Observed cardinality feeds capacity bounds through
    the operator's in-stream bound growth.

    The pre-resolution chunk is handed to the resolved executor through the
    same ``consume_async`` seam the stream uses, so ``auto`` inherits
    ingest overlap from its very first chunk."""

    SAMPLE_ROWS = 4096

    def __init__(self, plan: GroupByPlan):
        self._plan = plan
        self._inner = None
        self._resolved = None
        self._stats = adaptive.RunningStats(domain=plan.execution.key_domain)
        self._escalated = False

    @property
    def peak_buffered_chunks(self) -> int:
        return self._inner.peak_buffered_chunks if self._inner else 0

    def memory_stats(self) -> dict:
        return (
            self._inner.memory_stats() if self._inner
            else super().memory_stats()
        )

    @property
    def strategy_label(self) -> str:
        return self._inner.strategy_label if self._inner else "auto"

    @property
    def plan(self) -> GroupByPlan:
        return self._inner.plan if self._inner else self._plan

    def device_table_bytes(self) -> int:
        return self._inner.device_table_bytes() if self._inner else 0

    def event_counts(self):
        return self._inner.event_counts() if self._inner else None

    def stats(self) -> dict:
        return self._inner.stats() if self._inner else super().stats()

    def _sample_keys(self, chunk: Table) -> jnp.ndarray:
        head = Table({k: v[: self.SAMPLE_ROWS] for k, v in chunk.columns.items()})
        keys, _ = chunk_key_column(head, self._plan.keys, self._plan.raw_keys)
        return keys

    def _observe(self, chunk: Table) -> None:
        stats = self._stats.update(self._sample_keys(chunk))
        if self._inner is None:
            self._resolved = resolve_plan_stats(self._plan, stats)
            self._inner = make_executor(self._resolved)
            self._inner.open()
        else:
            self._maybe_replan(stats)

    def _maybe_replan(self, stats: adaptive.WorkloadStats) -> None:
        """hash→hybrid escalation on long streams: the first-chunk sample
        missed heavy-hitter mass that the running sketch has now observed.
        Only under GROW (the auto default) — adoption inserts the heavy keys
        into the live table, which must be allowed to widen for them."""
        if (
            self._escalated
            or not isinstance(self._inner, _ScanExecutor)
            or self._resolved.saturation != SaturationPolicy.GROW
            or not (stats.est_top_freq >= 0.25 and stats.est_groups > 4096)
        ):
            return
        heavy = self._stats.heavy_keys[: self._plan.execution.num_registers]
        if not heavy:
            return
        hybrid_plan = replace(
            self._resolved, strategy="hybrid",
            execution=replace(
                self._resolved.execution,
                heavy_keys=jnp.asarray(heavy, jnp.uint32),
            ),
        )
        self._inner = _HybridExecutor.adopt(hybrid_plan, self._inner._op)
        self._escalated = True

    def consume(self, chunk: Table) -> None:
        self._observe(chunk)
        self._inner.consume(chunk)

    def consume_async(self, chunk: Table):
        self._observe(chunk)
        return self._inner.consume_async(chunk)

    def poll(self, token) -> None:
        # tokens stay valid across an escalation: hybrid adopts the SAME
        # operator the tokens were dispatched on
        self._inner.poll(token)

    def finalize(self) -> Table:
        if self._inner is None:
            raise ValueError("GroupByPlan executed over zero chunks")
        return self._inner.finalize()


# ---------------------------------------------------------------------------
# concurrent: the scan-compiled morsel pipeline (streams natively)


class _ScanExecutor(_ExecutorBase):
    """Strategy ``concurrent`` (hash ticketing): a thin saturation-policy
    shell around the scan-compiled :class:`GroupByOperator`.  Streaming-
    native — no chunk is ever retained: ``grow`` rides the operator's
    in-stream bound growth (pause → widen ``key_by_ticket`` + accumulators →
    resume at the paused morsel), so a misestimated bound recovers without
    replaying the stream."""

    strategy_label = "concurrent"

    def __init__(self, plan: GroupByPlan):
        self._plan = plan
        p, ex = plan, plan.execution
        self._op = GroupByOperator(
            key_columns=list(p.keys), aggs=list(p.aggs), max_groups=p.max_groups,
            morsel_rows=ex.morsel_rows, update=ex.update or "scatter",
            use_kernel=ex.kernel == "scan_body" or ex.use_kernel,
            load_factor=ex.load_factor,
            pipeline=ex.pipeline, capacity=ex.capacity, raw_keys=p.raw_keys,
            check_overflow=p.saturation != SaturationPolicy.UNCHECKED,
            grow_bound=p.saturation == SaturationPolicy.GROW,
            collect_events=_instrument(plan),
        )

    def consume(self, chunk: Table) -> None:
        self._op.consume(chunk)

    def consume_async(self, chunk: Table):
        return self._op.consume_async(chunk)

    def poll(self, token) -> None:
        self._op.poll(token)

    def finalize(self) -> Table:
        out = self._op.finalize()
        self.publish()
        return out

    def device_table_bytes(self) -> int:
        return resize.table_nbytes(self._op._table) + sum(
            int(a.nbytes) for a in self._op._state.accs
        )

    def event_counts(self):
        return self._op.event_counts() if self._op.collect_events else None


# ---------------------------------------------------------------------------
# batched co-dispatch: N same-shape queries, ONE device launch per step
#
# The serving scheduler (serve/scheduler.py) co-schedules slot tasks that
# share a ``batch_key``.  For GROUP BY streams the key is ``batch_signature``
# below: plans with equal signatures run the SAME scan body over
# identically-shaped (TicketTable, AggState) carries, so one chunk from each
# of N queries can fold in a single jitted dispatch — stack the raw chunk
# columns, stage + scan every lane inside one jit — amortizing N per-chunk
# launch overheads into one (the continuous-batching speedup bench_serve.py
# measures).


def batch_signature(plan: GroupByPlan):
    """Hashable co-dispatch key, or ``None`` when the plan is ineligible.

    Eligible: the scan-compiled concurrent pipeline with hash ticketing and
    a fixed bound — RAISE and UNCHECKED saturation only.  GROW needs
    per-query host control flow (pause → migrate → resume) that cannot ride
    a shared fused dispatch, kernels/host pipelines have their own launch
    story, and sort/direct ticketing does not carry a probe table.  Two
    plans with the same signature produce bit-identical per-query results
    under batched stepping because each fused lane IS the sequential scan
    body (same op order, same scatters).  Instrumented plans are ineligible:
    ``_batched_consume`` does not thread the per-query event vector, and a
    fused lane that silently stopped counting would corrupt the registry.
    """
    if _instrument(plan):
        return None
    ex = plan.execution
    saturation = plan.saturation or (
        SaturationPolicy.GROW if plan.max_groups is None else SaturationPolicy.RAISE
    )
    if (
        plan.strategy != "concurrent"
        or plan.max_groups is None
        or ex.ticketing != "hash"
        or ex.pipeline != "scan"
        or ex.use_kernel
        or ex.kernel not in (None, "off")
        or saturation not in (SaturationPolicy.RAISE, SaturationPolicy.UNCHECKED)
    ):
        return None
    return (
        "scan",
        plan.max_groups,
        ex.capacity or table_capacity(plan.max_groups, ex.load_factor),
        ex.morsel_rows,
        ex.update or "scatter",
        expand_agg_specs(plan.aggs),
        saturation == SaturationPolicy.RAISE,
    )


@functools.partial(
    jax.jit,
    static_argnames=("raw_keys", "morsel_rows", "vcols", "update_fn", "check"),
)
def _batched_consume(tables, states, key_cols, val_cols, *, raw_keys,
                     morsel_rows, vcols, update_fn, check):
    """Fold chunk_i into (table_i, state_i) for every query in ONE dispatch.

    The host hands over the RAW stacked chunk columns (each leaf
    ``(n_queries, rows)``); key canonicalization, morsel padding and the
    probe→ticket→update scan all run inside this single jitted call —
    staged per-query on the host they cost more than the dispatches the
    batching saves.  Lanes are compiled UNROLLED, not vmapped: a vmapped
    probe ``while_loop`` runs every lane in lockstep to the worst lane's
    probe count, which erases the win.  Each lane replays exactly the solo
    path's op sequence (same canonicalization, same EMPTY padding, same
    scan body), so per-query results are bit-identical to sequential
    stepping.  ``check=True`` keeps RAISE's sticky device-side loss flag
    per lane (a saturated probe table or a bound overflow poisons only
    that query's finalize)."""
    n_rows = key_cols[0].shape[1]
    nm = max(-(-n_rows // morsel_rows), 1)
    pad = nm * morsel_rows - n_rows

    def stage(i):
        # chunk_key_column + morselize_chunk, inlined per lane
        if raw_keys:
            keys = key_cols[0][i].reshape(-1).astype(jnp.uint32)
        else:
            keys = combine_keys(*(kc[i] for kc in key_cols))
        if pad:
            keys = jnp.concatenate(
                [keys, jnp.full((pad,), EMPTY_KEY, keys.dtype)]
            )
        vm = []
        for vc in val_cols:
            v = vc[i].astype(jnp.float32)
            if pad:
                v = jnp.concatenate([v, jnp.zeros((pad,), jnp.float32)])
            vm.append(v.reshape(nm, morsel_rows))
        return keys.reshape(nm, morsel_rows), tuple(vm)

    def one(table, state, km, vm):
        def body(carry, xs):
            table, state = carry
            k, vt = xs
            tickets, table = tk.get_or_insert(table, k)
            if check:
                dropped = jnp.any((tickets < 0) & (k != jnp.uint32(EMPTY_KEY)))
                table = table._replace(overflowed=table.overflowed | dropped)
            state = up.update_agg_state(
                state, tickets, dict(zip(vcols, vt)), update_fn
            )
            return (table, state), None

        (table, state), _ = jax.lax.scan(body, (table, state), (km, vm))
        return table, state

    outs = []
    for i, (table, state) in enumerate(zip(tables, states)):
        km, vm = stage(i)
        outs.append(one(table, state, km, vm))
    return tuple(o[0] for o in outs), tuple(o[1] for o in outs)


def consume_batched(executors, chunks) -> None:
    """Consume ``chunks[i]`` into ``executors[i]`` — one device dispatch for
    the whole batch.  Every executor must come from plans with the SAME
    ``batch_signature`` (the scheduler guarantees it).  The fast path
    requires the round's chunks to share a row count and carry no
    ``__mask__`` column: the raw columns stack in one op per column and
    everything else happens inside the jit.  Ragged rounds (a stream's
    short final chunk) fall back to per-query consumes — correctness never
    depends on the fast path."""
    assert len(executors) == len(chunks) >= 1
    ops = [x._op for x in executors]
    ref = ops[0]
    if (
        len(ops) == 1
        or len({c.num_rows for c in chunks}) != 1
        or any("__mask__" in c.columns for c in chunks)
    ):
        for x, chunk in zip(executors, chunks):
            x.consume(chunk)
        return
    vcols = tuple(sorted({c for c, _ in ref._state.specs if c is not None}))
    key_cols = tuple(
        jnp.stack([c[k] for c in chunks]) for k in ref.key_columns
    )
    val_cols = tuple(jnp.stack([c[v] for c in chunks]) for v in vcols)
    new_tables, new_states = _batched_consume(
        tuple(op._table for op in ops), tuple(op._state for op in ops),
        key_cols, val_cols,
        raw_keys=ref.raw_keys, morsel_rows=ref.morsel_rows, vcols=vcols,
        update_fn=ref._update_fn, check=ref.check_overflow,
    )
    for op, table, state in zip(ops, new_tables, new_states):
        op._table, op._state = table, state


class _BufferedExecutor(_ExecutorBase):
    """Shared chunk-buffering consume for the genuinely ONE-SHOT strategies
    (sort/direct ticketing): sorting and perfect-hash occupancy checks are
    pipeline breakers over the full input, so chunks accumulate and the
    strategy pipeline runs at finalize.  Tracks its buffer high-water mark
    so streaming tests/benchmarks can assert who buffers and who doesn't."""

    def __init__(self, plan: GroupByPlan):
        self._plan = plan
        self._keys, self._vals, self._rows = [], [], 0
        self.peak_buffered_chunks = 0
        self.peak_retained_bytes = 0

    def consume(self, chunk: Table) -> None:
        keys, vals = _chunk_keys_values(self._plan, chunk)
        self._rows += int(keys.shape[0])
        self._keys.append(keys)
        self._vals.append(vals)
        self.peak_buffered_chunks = max(self.peak_buffered_chunks, len(self._keys))
        self.peak_retained_bytes += int(keys.nbytes) + sum(
            int(v.nbytes) for v in vals.values()
        )

    def _gathered(self):
        keys = _concat(self._keys)
        vals = {c: _concat([v[c] for v in self._vals])
                for c in value_columns(self._plan.aggs)}
        return keys, vals


class _SortExecutor(_BufferedExecutor):
    """Strategy ``concurrent`` with sort-based ticketing.  Sorting is a
    genuine pipeline breaker (tickets are global sort ranks), so this is
    the one remaining one-shot executor: chunks buffer and the pipeline
    runs at finalize."""

    strategy_label = "sort"

    def finalize(self) -> Table:
        p, ex = self._plan, self._plan.execution
        keys, vals = self._gathered()
        max_groups = p.max_groups
        tickets, kbt, count = tk.sort_ticketing(keys)
        if p.saturation != SaturationPolicy.UNCHECKED:
            issued = int(jax.device_get(count))
            if issued > max_groups:
                if p.saturation == SaturationPolicy.RAISE:
                    raise _overflow_error(issued, max_groups)
                max_groups = _next_bound(max_groups, self._rows, issued=issued)
        update_fn = up.get_update_fn(ex.update or "scatter")
        state = up.init_agg_state(expand_agg_specs(p.aggs), max_groups)
        state = up.update_agg_state(state, tickets, vals, update_fn)
        return build_result_table(p.aggs, state.get, kbt, count, max_groups)


class _DirectExecutor(_ExecutorBase):
    """Strategy ``concurrent`` with perfect-hash (direct) ticketing,
    STREAMING: ticket == key, so tickets are stable across chunks and under
    domain growth — each chunk folds straight into the carried ``AggState``
    and no chunk is ever retained (the one-shot buffering this ticketing
    used to share with sort was an artifact, not a data dependency).

    RAISE/UNCHECKED consume with zero host syncs: out-of-domain drops and
    occupancy past the bound accumulate in device-side sticky flags, read
    once at finalize by the raise policy.  GROW syncs per chunk BEFORE
    updating: an out-of-range chunk widens the domain to cover the largest
    observed key (same rows-bounded limit as every other grow — a key space
    far sparser than the row count means direct is the wrong ticketing),
    pads the accumulators (tickets unaffected), and re-tickets only the
    current chunk."""

    strategy_label = "direct"

    def __init__(self, plan: GroupByPlan):
        if not plan.raw_keys:
            # direct ticketing is ticket == key: hash-combined keys leave
            # the bounded domain, so every row would silently miss
            raise ValueError(
                "ticketing='direct' requires raw_keys=True (a single "
                "bounded-domain uint32 key column)"
            )
        self._plan = plan
        ex = plan.execution
        self._domain = ex.key_domain or plan.max_groups
        self._bound = plan.max_groups
        self._update_fn = up.get_update_fn(ex.update or "scatter")
        self._state = None
        self._rows = 0
        self._dropped = jnp.zeros((), jnp.bool_)   # sticky: out-of-domain rows
        self._max_ticket = jnp.full((), -1, jnp.int32)

    def consume(self, chunk: Table) -> None:
        p = self._plan
        keys, vals = _chunk_keys_values(p, chunk)
        self._rows += int(keys.shape[0])
        if self._state is None:
            self._state = up.init_agg_state(
                expand_agg_specs(p.aggs), self._bound
            )
        tickets, _, _ = tk.direct_ticketing(keys, self._domain)
        valid = keys != jnp.uint32(EMPTY_KEY)
        if p.saturation == SaturationPolicy.GROW:
            dropped, used = jax.device_get((
                jnp.any((tickets < 0) & valid),
                jnp.max(jnp.concatenate(
                    [tickets.reshape(-1), jnp.full((1,), -1, jnp.int32)]
                )) + 1,
            ))
            if bool(dropped) or int(used) > self._bound:
                # the domain must cover the largest observed key VALUE;
                # direct allocates O(domain) arrays, so keep the same
                # rows-bound as every other grow
                kmax = int(jax.device_get(
                    jnp.max(jnp.where(valid, keys, jnp.uint32(0)))
                ))
                limit = max(4 * self._rows, 65536)
                if kmax + 1 > limit:
                    raise GroupByOverflowError(
                        f"direct-ticketing overflow: observed key {kmax} "
                        f"needs domain {kmax + 1}, past the rows-bounded "
                        f"growth limit {limit} — the key space is too "
                        "sparse for perfect-hash ticketing; use "
                        "ticketing='hash' instead."
                    )
                self._domain = max(kmax + 1, self._domain)
                # bound never shrinks mid-stream: earlier chunks already
                # committed accumulator slots up to the current bound
                self._bound = max(self._domain, self._bound, 64)
                self._state = up.grow_agg_state(self._state, self._bound)
                tickets, _, _ = tk.direct_ticketing(keys, self._domain)
        else:
            self._dropped = self._dropped | jnp.any((tickets < 0) & valid)
            self._max_ticket = jnp.maximum(
                self._max_ticket, jnp.max(jnp.concatenate(
                    [tickets.reshape(-1), jnp.full((1,), -1, jnp.int32)]
                ))
            )
        self._state = up.update_agg_state(
            self._state, tickets, vals, self._update_fn
        )

    def finalize(self) -> Table:
        p = self._plan
        if self._state is None:
            raise ValueError("GroupByPlan executed over zero chunks")
        domain, max_groups = self._domain, self._bound
        _, kbt, count = tk.direct_ticketing(
            jnp.zeros((0,), jnp.uint32), domain
        )
        if p.saturation == SaturationPolicy.RAISE:
            dropped, used = jax.device_get((self._dropped, self._max_ticket + 1))
            if bool(dropped) or int(used) > max_groups:
                raise GroupByOverflowError(
                    "direct-ticketing overflow: keys outside "
                    f"domain={domain} or past max_groups={max_groups} "
                    "would be dropped. Use SaturationPolicy.GROW or "
                    "declare a larger key_domain/max_groups."
                )
        if p.saturation != SaturationPolicy.UNCHECKED:
            # checked reads promise count ≤ materialized rows (legacy
            # unchecked keeps the raw static-domain count)
            count = jnp.minimum(count, max_groups)
        return build_result_table(p.aggs, self._state.get, kbt, count, max_groups)

    def device_table_bytes(self) -> int:
        if self._state is None:
            return 0
        return sum(int(a.nbytes) for a in self._state.accs)


# ---------------------------------------------------------------------------
# hybrid: heavy-hitter registers + concurrent tail (streams natively)


@functools.partial(jax.jit, static_argnames=("kinds",))
def _hybrid_registers(heavy, km, vm, regs, *, kinds):
    """Fold one morselized chunk into the per-heavy-key dense registers.

    Scans the morsel axis so the compare matrix is (R, morsel_rows) per
    step — O(R·morsel) live memory instead of materializing (R, N).
    Returns the updated registers and the per-row heavy mask (morsel
    layout), which the caller uses to strip heavy rows from the tail.
    """

    def body(carry, xs):
        regs = carry
        k, vs = xs
        live = (k != jnp.uint32(EMPTY_KEY))[None, :]
        is_heavy = (k[None, :] == heavy[:, None]) & live      # (R, morsel)
        out = []
        for kind, acc, v in zip(kinds, regs, vs):
            vb = v[None, :]
            if kind == "count":
                out.append(acc + jnp.sum(is_heavy.astype(jnp.float32), axis=1))
            elif kind == "sum":
                out.append(acc + jnp.sum(jnp.where(is_heavy, vb, 0.0), axis=1))
            elif kind == "min":
                out.append(jnp.minimum(acc, jnp.min(jnp.where(is_heavy, vb, jnp.inf), axis=1)))
            else:
                out.append(jnp.maximum(acc, jnp.max(jnp.where(is_heavy, vb, -jnp.inf), axis=1)))
        return tuple(out), jnp.any(is_heavy, axis=0)

    return jax.lax.scan(body, regs, (km, vm))


class _HybridExecutor(_ExecutorBase):
    """Strategy ``hybrid``: rows matching a small heavy-hitter candidate set
    accumulate into dense per-key registers (masked reductions — zero
    conflicts, the extreme thread-local case); the remaining tail flows
    through the scan-compiled concurrent pipeline, which the heavy-hitter
    removal has just stripped of its only contention source.  Streams
    natively: ``grow`` rides the tail operator's in-stream bound growth and
    no chunks are retained."""

    strategy_label = "hybrid"

    def __init__(self, plan: GroupByPlan):
        self._plan = plan
        self._specs = expand_agg_specs(plan.aggs)
        self._kinds = tuple(k for _, k in self._specs)
        self._vcols = value_columns(plan.aggs)
        hk = plan.execution.heavy_keys
        self._heavy = None if hk is None else jnp.asarray(hk).reshape(-1).astype(jnp.uint32)
        self._regs = None
        self._op = None

    @classmethod
    def adopt(cls, plan: GroupByPlan, op: GroupByOperator) -> "_HybridExecutor":
        """Mid-stream escalation handoff (auto re-planning): adopt a live
        concurrent operator — table, accumulators, grown bound and any
        in-flight tokens stay valid — as the tail pipeline.  The heavy keys
        (``plan.execution.heavy_keys``) get tickets NOW (idempotent for
        keys already seen); registers start at identity, because every
        pre-switch heavy row is already counted in the tail accumulators.
        """
        self = cls(plan)
        assert self._heavy is not None, "adopt() requires pinned heavy_keys"
        if self._heavy.shape[0] == 0:
            self._heavy = jnp.full((1,), EMPTY_KEY, jnp.uint32)
        # The tail now arrives pre-canonicalized (the register stripper runs
        # on the hash-combined key column), so the operator switches to the
        # raw ``__key__`` calling convention — the key SPACE is unchanged.
        op.key_columns = ["__key__"]
        op.raw_keys = True
        if _instrument(plan) and not op.collect_events:
            # adopted mid-stream: pre-switch counts are lost (the adopted
            # operator ran uninstrumented), post-switch counts are exact
            op.collect_events = True
            op._events = obs_metrics.zero_event_vector()
        if op.grow_bound:
            op._grow(int(self._heavy.shape[0]))  # headroom for the inserts
        _, op._table = tk.get_or_insert(op._table, self._heavy)
        self._op = op
        self._regs = tuple(
            up.init_acc(self._heavy.shape[0], k) for k in self._kinds
        )
        return self

    def _make_op(self, max_groups: int) -> GroupByOperator:
        p, ex = self._plan, self._plan.execution
        op = GroupByOperator(
            key_columns=["__key__"], aggs=list(p.aggs), max_groups=max_groups,
            morsel_rows=ex.morsel_rows, update=ex.update or "scatter",
            use_kernel=ex.use_kernel, load_factor=ex.load_factor,
            pipeline=ex.pipeline, capacity=ex.capacity, raw_keys=True,
            check_overflow=p.saturation != SaturationPolicy.UNCHECKED,
            grow_bound=p.saturation == SaturationPolicy.GROW,
            collect_events=_instrument(p),
        )
        # Heavy keys own the FIRST tickets: a key whose every occurrence is
        # absorbed by the register path still gets counted, and the register
        # merge is a plain ticket-indexed scatter at finalize.
        _, table = tk.get_or_insert(op._table, self._heavy)
        op._table = table
        return op

    def consume(self, chunk: Table) -> None:
        self._op_poll(self.consume_async(chunk))

    def _op_poll(self, token):
        if token is not None:
            self._op.poll(token)

    def consume_async(self, chunk: Table):
        from repro.core.hybrid import detect_heavy_hitters

        keys, vals = _chunk_keys_values(self._plan, chunk)
        n = int(keys.shape[0])
        if self._heavy is None:
            heavy = detect_heavy_hitters(keys, self._plan.execution.num_registers)
            self._heavy = jnp.asarray(heavy).reshape(-1).astype(jnp.uint32)
        if self._heavy.shape[0] == 0:
            self._heavy = jnp.full((1,), EMPTY_KEY, jnp.uint32)
        if self._op is None:
            self._regs = tuple(
                up.init_acc(self._heavy.shape[0], k) for k in self._kinds
            )
            self._op = self._make_op(self._plan.max_groups)
        from repro.engine.morsels import morselize_chunk

        km, vm, _ = morselize_chunk(keys, vals, self._plan.execution.morsel_rows)
        vtuple = tuple(
            vm[c] if c is not None else jnp.ones(km.shape, jnp.float32)
            for c, _ in self._specs
        )
        self._regs, hmask = _hybrid_registers(
            self._heavy, km, vtuple, self._regs, kinds=self._kinds
        )
        tail = jnp.where(hmask.reshape(-1)[:n], jnp.uint32(EMPTY_KEY), keys)
        tail_chunk = Table({"__key__": tail, **{c: vals[c] for c in self._vcols}})
        return self._op.consume_async(tail_chunk)

    def poll(self, token) -> None:
        self._op_poll(token)

    def _merged_state(self) -> up.AggState:
        """Tail accumulators with the registers scattered into their ticket
        slots — a pure function of the live state, so ``finalize`` stays an
        idempotent read (stream-safe)."""
        op = self._op
        heavy_tickets = tk.lookup(op._table, self._heavy)  # -1 for padding
        accs = []
        for (_, kind), acc, reg in zip(op._state.specs, op._state.accs, self._regs):
            merge_kind = "sum" if kind in ("sum", "count") else kind
            accs.append(up.scatter_update(acc, heavy_tickets, reg, kind=merge_kind))
        return up.AggState(op._state.specs, tuple(accs))

    def finalize(self) -> Table:
        if self._op is None:
            raise ValueError("GroupByPlan executed over zero chunks")
        op = self._op
        tail_state = op._state
        op._state = self._merged_state()
        try:
            return op.finalize()
        finally:
            # registers stay separate: consume may continue after a read
            op._state = tail_state

    def device_table_bytes(self) -> int:
        if self._op is None:
            return 0
        return (
            resize.table_nbytes(self._op._table)
            + sum(int(a.nbytes) for a in self._op._state.accs)
            + sum(int(r.nbytes) for r in (self._regs or ()))
        )

    def event_counts(self):
        if self._op is None or not self._op.collect_events:
            return None
        # tail-pipeline counts only: register-absorbed heavy rows never
        # enter the scan, so ``rows`` here reads as "tail rows"
        return self._op.event_counts()


# ---------------------------------------------------------------------------
# incremental merge executors: per-chunk strategy pipeline + carried table
# (pallas, partitioned)


class _IncrementalMergeExecutor(_ExecutorBase):
    """Streaming shell for strategies whose pipeline is a one-shot program
    over its input (kernel launches, worker exchanges): run the pipeline
    over EACH chunk, then merge the chunk's bounded partial result (at most
    ``max_groups`` (key, partial) entries) into a carried ticket table +
    merge accumulators.  State is O(max_groups); no chunks are retained.

    Saturation: the per-chunk pipeline recovers chunk-locally under GROW
    (strategy-specific, one blocking sync per chunk); the carried UNION
    bound grows by padding ``key_by_ticket`` and the merge accumulators
    (tickets are stable) before a chunk that could overflow it merges.
    RAISE accumulates sticky device-side flags and checks once at finalize
    (zero per-chunk syncs); UNCHECKED never syncs and truncates.

    The FIRST chunk's raw partial is held un-merged (still O(max_groups),
    not the chunk) and lowered into the carried table only when a second
    chunk arrives: single-chunk executions — every legacy adapter —
    materialize the strategy's NATIVE layout bit-for-bit (the Pallas fuzzy
    ticketer's gapped ticket ranges survive; the merge would compact them).
    """

    def __init__(self, plan: GroupByPlan):
        self._plan = plan
        self._specs = expand_agg_specs(plan.aggs)
        self._max_groups = plan.max_groups          # carried union bound
        self._chunk_bound = plan.max_groups         # per-chunk pipeline bound
        self._rows = 0
        self._host_count = 0                        # union count mirror (GROW)
        self._ovf = jnp.zeros((), jnp.bool_)        # sticky chunk-loss flag
        self._pending = None                        # first chunk's raw partial
        self._merged_any = False
        self._table = tk.make_table(
            table_capacity(plan.max_groups, plan.execution.load_factor),
            max_groups=plan.max_groups,
        )
        self._accs = {
            spec: up.init_acc(plan.max_groups, spec[1]) for spec in self._specs
        }

    # subclass: run the strategy pipeline over one chunk, honoring
    # ``self._chunk_bound`` (and growing it under GROW); returns
    # (key_by_ticket, {spec: raw partial acc}, count, device ovf flag)
    def _chunk_partial(self, keys, vals):
        raise NotImplementedError

    def _grow_carried(self, new_max: int) -> None:
        from repro.core import resize

        self._table = resize.grow_bound(
            self._table, new_max, self._plan.execution.load_factor
        )
        for spec, acc in self._accs.items():
            pad = jnp.full((new_max - acc.shape[0],), up.neutral(spec[1]), acc.dtype)
            self._accs[spec] = jnp.concatenate([acc, pad])
        self._max_groups = new_max

    def _merge(self, partial) -> None:
        p = self._plan
        kbt, partials, count, ovf = partial
        if p.saturation == SaturationPolicy.GROW:
            issued = int(jax.device_get(count))
            if self._host_count + issued > self._max_groups:
                self._grow_carried(
                    max(4 * self._max_groups, self._host_count + issued, 64)
                )
        tickets, self._table = tk.get_or_insert(self._table, kbt)
        for spec, acc in partials.items():
            merge_kind = _MERGE_KIND[spec[1]]
            self._accs[spec] = up.scatter_update(
                self._accs[spec], tickets, acc, kind=merge_kind
            )
        if p.saturation == SaturationPolicy.GROW:
            self._host_count = int(jax.device_get(self._table.count))
        else:
            self._ovf = self._ovf | ovf
        self._merged_any = True

    def consume(self, chunk: Table) -> None:
        keys, vals = _chunk_keys_values(self._plan, chunk)
        self._rows += int(keys.shape[0])
        partial = self._chunk_partial(keys, vals)
        if not self._merged_any and self._pending is None:
            self._pending = partial  # single-chunk fast path: native layout
            # the held raw partial IS retained state beyond the in-flight
            # window (O(max_groups), not the chunk) — report it, don't
            # under-count relative to the buffering executors
            kbt, partials, _, _ = partial
            self.peak_retained_bytes = max(
                self.peak_retained_bytes,
                int(kbt.nbytes) + sum(int(a.nbytes) for a in partials.values()),
            )
            return
        if self._pending is not None:
            pending, self._pending = self._pending, None
            self._merge(pending)
        self._merge(partial)

    def finalize(self) -> Table:
        p = self._plan
        if self._pending is not None and not self._merged_any:
            # Exactly one chunk consumed: the strategy's own materialization,
            # bit-identical to the pre-streaming executors (legacy adapters).
            kbt, partials, count, ovf = self._pending
            if p.saturation != SaturationPolicy.UNCHECKED and bool(
                jax.device_get(ovf)
            ):
                raise _overflow_error(int(jax.device_get(count)), self._chunk_bound)
            return build_result_table(
                p.aggs, lambda c, k: partials[(c, k)], kbt, count,
                self._chunk_bound,
            )
        if p.saturation != SaturationPolicy.UNCHECKED:
            lost, union_ovf, count = jax.device_get(
                (self._ovf, self._table.overflowed, self._table.count)
            )
            if bool(lost) or bool(union_ovf):
                raise _overflow_error(int(count), self._max_groups)
        return build_result_table(
            p.aggs, lambda c, k: self._accs[(c, k)],
            self._table.key_by_ticket, self._table.count, self._max_groups,
        )

    def device_table_bytes(self) -> int:
        n = resize.table_nbytes(self._table) + sum(
            int(a.nbytes) for a in self._accs.values()
        )
        if self._pending is not None:
            kbt, partials, _, _ = self._pending
            n += int(kbt.nbytes) + sum(int(a.nbytes) for a in partials.values())
        return n


class _PallasExecutor(_IncrementalMergeExecutor):
    """``kernel="split"`` (legacy strategy ``pallas``): the VMEM-resident
    ticket kernel + segment-update kernel (kernels/ops.py) launched per
    chunk; the kernel's table state lives only for one launch, so each
    chunk's bounded result merges into the carried table.  GROW re-launches
    the CHUNK with a grown bound/capacity (migrate == rebuild here) — never
    the stream.  The fused route (:class:`_FusedExecutor`) supersedes this
    for production use: it carries the table ACROSS chunks in VMEM instead
    of rebuilding + merging per chunk."""

    strategy_label = "pallas"

    def __init__(self, plan: GroupByPlan):
        super().__init__(plan)
        ex = plan.execution
        self._capacity = ex.capacity or table_capacity(
            plan.max_groups, ex.load_factor
        )

    def _chunk_partial(self, keys, vals):
        from repro.kernels import ops as kops

        p, ex = self._plan, self._plan.execution
        bound, capacity = self._chunk_bound, self._capacity
        while True:
            tickets, kbt, count = kops._ticket(
                keys, capacity=capacity, max_groups=bound,
                morsel_size=ex.morsel_size, interpret=ex.interpret,
            )
            dropped_dev = jnp.any((tickets < 0) & (keys != jnp.uint32(EMPTY_KEY)))
            ovf = (count > bound) | dropped_dev
            if p.saturation != SaturationPolicy.GROW:
                break
            issued = int(jax.device_get(count))
            dropped = bool(jax.device_get(dropped_dev))
            if issued <= bound and not dropped:
                break
            # GROW: the two overflow causes recover independently — an
            # undersized bound grows max_groups (rows-bounded), a saturated
            # probe table doubles capacity (the kernel-world migrate)
            grew = False
            if issued > bound and bound < self._rows:
                bound = _next_bound(bound, self._rows)
                grew = True
            if dropped:
                capacity = max(table_capacity(bound, ex.load_factor), 2 * capacity)
                grew = True
            if not grew:
                raise GroupByOverflowError(
                    f"GROUP BY overflow: {issued} tickets issued against "
                    f"max_groups={bound} and growth cannot make progress."
                )
        self._chunk_bound, self._capacity = bound, capacity
        partials = {}
        for col, kind in self._specs:
            v = vals[col] if col else jnp.ones(keys.shape, jnp.float32)
            partials[(col, kind)] = kops._segment_aggregate(
                tickets, v, num_groups=bound, kind=kind,
                strategy=ex.update or "scatter", morsel_size=ex.morsel_size,
                interpret=ex.interpret,
            )
        return kbt, partials, count, ovf


class _FusedExecutor(_ExecutorBase):
    """``kernel="fused"``: THE production Pallas route — ticketing and
    aggregation fused in one VMEM-resident kernel (kernels/fused_groupby.py)
    whose table + accumulators persist ACROSS chunks as carried device
    state, exactly like the scan pipeline carries its TicketTable.  Nothing
    is rebuilt or merged per chunk; the only per-chunk work is the morsels
    themselves.

    ``kernel_programs > 1`` runs per-grid-program local tables (two-level
    design); ``finalize``/``snapshot`` perform the second-level merge into
    one global ticket space.  Saturation rides the kernel's §4.4 info
    vector: ``poll`` reads the per-program halt signals once per chunk (the
    scan route's sync cadence), grows bound/capacity host-side via
    ``grow_fused_state`` (table migration preserves tickets, so committed
    aggregates are untouched) and relaunches the chunk at each program's
    first halted morsel.  RAISE surfaces the same sticky overflow as the
    scan pipeline; UNCHECKED never syncs."""

    strategy_label = "fused"

    def __init__(self, plan: GroupByPlan):
        from repro.kernels import fused_groupby as fk

        self._fk = fk
        self._plan = plan
        ex = plan.execution
        self._specs = expand_agg_specs(plan.aggs)
        self._kinds = tuple(k for _, k in self._specs)
        self._vcols = tuple(value_columns(plan.aggs))
        # accumulator → value-plane map (-1: count consumes no plane — a
        # mean's count half carries its column name but still counts rows)
        self._kspecs = tuple(
            (-1 if kind == "count" or not col else self._vcols.index(col), kind)
            for col, kind in self._specs
        )
        self._m = ex.morsel_size
        self._P = ex.kernel_programs
        self._lf = ex.load_factor
        self._interpret = ex.interpret
        self._checked = plan.saturation != SaturationPolicy.UNCHECKED
        self._grow = plan.saturation == SaturationPolicy.GROW
        self._collect = _instrument(plan)
        self._rows = 0
        self._migrations = 0
        self._bound_grows = 0
        self._state = fk.init_fused_state(
            capacity=ex.capacity or table_capacity(plan.max_groups, self._lf),
            max_groups=plan.max_groups,
            kinds=self._kinds,
            programs=self._P,
        )
        self._info = None        # (P, INFO_LEN) control vector, latest launch
        # FIFO of launches whose halt signals are unread:
        # [km, vm, info, grow_gen].  Prefetch dispatches chunk k+1 before
        # chunk k's poll, so a grow pause must be able to replay EVERY
        # chunk launched since the last drain, each from its own recorded
        # halt morsel — a single last-chunk slot would drop the earlier
        # chunk's unreplayed tail.
        self._pending: list = []
        self._grow_gen = 0       # bumps per grow; stamps pending launches

    def _morselize(self, keys, vals):
        """Pad + reshape one chunk into (P·npm, M) key morsels and
        (V, P·npm, M) value planes; program ``p`` owns the contiguous
        morsel range [p·npm, (p+1)·npm)."""
        n = keys.shape[0]
        step = self._m * self._P
        pad = (-n) % step
        k = keys.astype(jnp.uint32)
        if pad:
            k = jnp.concatenate([k, jnp.full((pad,), EMPTY_KEY, jnp.uint32)])
        km = k.astype(jnp.int32).reshape(-1, self._m)
        if self._vcols:
            planes = []
            for c in self._vcols:
                v = vals[c]
                if pad:
                    v = jnp.concatenate([v, jnp.zeros((pad,), jnp.float32)])
                planes.append(v.reshape(-1, self._m))
            vm = jnp.stack(planes)
        else:
            # the kernel's value operand needs ≥1 plane; count-only plans
            # never read it (plane index -1)
            vm = jnp.zeros((1, km.shape[0], self._m), jnp.float32)
        return km, vm

    def _launch(self, km, vm, starts) -> None:
        st = self._state
        self._state, self._info = self._fk.fused_consume(
            st, km, vm, starts,
            specs=self._kspecs,
            checked=self._checked,
            grow_bound=self._grow,
            # NOT clamped at 0: a bound below the morsel size must pause the
            # very first morsel (count 0 > negative slack) — running it
            # would issue tickets past the bound and drop their
            # key_by_ticket scatters, losing keys that GROW cannot recover
            threshold=int(self._lf * st.capacity),
            bound_slack=st.max_groups - self._m,
            collect_events=self._collect,
            interpret=self._interpret,
        )

    def consume_async(self, chunk: Table):
        keys, vals = _chunk_keys_values(self._plan, chunk)
        self._rows += int(keys.shape[0])
        km, vm = self._morselize(keys, vals)
        self._launch(km, vm, jnp.zeros((self._P,), jnp.int32))
        if self._checked:
            self._pending.append([km, vm, self._info, self._grow_gen])
        return self._info

    def consume(self, chunk: Table) -> None:
        self.poll(self.consume_async(chunk))

    def poll(self, token) -> None:
        """Drain the halt signals of EVERY launch since the last drain, in
        dispatch order (§4.4 pause protocol, host side).  Prefetch can put
        several chunks in flight before the first poll; a launch that ran
        clean costs one info read and is dropped, a halted one replays from
        each program's first halted morsel — exact, because the kernel's
        room check halts BEFORE a morsel commits and is monotone in the
        table count, so a chunk dispatched after a halted one committed
        nothing past its own recorded halt either.  An entry halted under a
        state the queue has since grown is relaunched once before growing
        again (``_grow_gen``), so a burst of stale halts can't cascade into
        spurious capacity doublings.  Zero reads when UNCHECKED."""
        if not self._checked:
            return
        fk = self._fk
        while self._pending:
            entry = self._pending[0]
            while True:
                km, vm, inf, gen = entry
                info = np.asarray(jax.device_get(inf))
                halted = info[:, fk.INFO_HALTED] != 0
                if not halted.any():
                    break
                cmax = int(info[:, fk.INFO_COUNT].max())
                if not self._grow:
                    raise _overflow_error(cmax, self._state.max_groups)
                if gen == self._grow_gen:
                    st = self._state
                    new_g, new_c = st.max_groups, st.capacity
                    if cmax > st.max_groups - self._m:
                        # bound headroom: the scan pipeline's blind-retry jump
                        new_g = max(4 * st.max_groups, cmax + self._m, 64)
                    if cmax > int(self._lf * st.capacity) or new_g == st.max_groups:
                        # capacity pressure — or a mid-morsel saturation below
                        # both thresholds (probe clustering): force the
                        # doubling so the replay is guaranteed progress
                        new_c = 2 * st.capacity
                    new_c = max(new_c, table_capacity(new_g, self._lf))
                    if new_g > st.max_groups:
                        self._bound_grows += 1
                    if new_c > st.capacity:
                        self._migrations += 1
                    self._state = fk.grow_fused_state(
                        st, self._kinds, new_max_groups=new_g,
                        new_capacity=new_c, load_factor=self._lf,
                    )
                    self._grow_gen += 1
                npm = km.shape[0] // self._P
                starts = jnp.asarray(
                    np.minimum(info[:, fk.INFO_FIRST_HALT], npm), jnp.int32
                )
                self._launch(km, vm, starts)
                entry[2], entry[3] = self._info, self._grow_gen
            self._pending.pop(0)

    def _merged(self):
        fk = self._fk
        counts = np.asarray(jax.device_get(self._state.count))
        target = self._state.max_groups
        if self._P > 1:
            # the union of P local ticket spaces can exceed one local bound;
            # GROW widens the merge target, RAISE detects via the merged
            # table's own sticky overflow below
            total = int(counts.sum())
            if self._grow and total > target:
                target = total
        table, accs = fk.merge_fused_state(
            self._state, self._kinds, max_groups=target,
            load_factor=self._lf,
        )
        overflowed = bool(counts.max(initial=0) > self._state.max_groups)
        if self._checked and (
            overflowed or bool(jax.device_get(table.overflowed))
        ):
            raise _overflow_error(int(jax.device_get(table.count)), target)
        return table, accs, target

    def finalize(self) -> Table:
        self.poll(self._info)
        table, accs, bound = self._merged()
        acc_by_spec = dict(zip(self._specs, accs))
        out = build_result_table(
            self._plan.aggs, lambda c, k: acc_by_spec[(c, k)],
            table.key_by_ticket, table.count, bound,
        )
        self.publish()
        return out

    def device_table_bytes(self) -> int:
        return self._state.nbytes()

    def event_counts(self) -> dict | None:
        if not self._collect:
            return None
        vec, counts = jax.device_get((self._state.events, self._state.count))
        out = obs_metrics.event_vector_to_dict(np.asarray(vec).sum(axis=0))
        count = int(np.asarray(counts).sum())
        out["migrations"] = self._migrations
        out["bound_grows"] = self._bound_grows
        out["num_groups"] = count
        out["table_capacity"] = self._state.capacity
        out["table_load_factor"] = count / self._state.capacity
        return out


class _PartitionedExecutor(_IncrementalMergeExecutor):
    """Strategy ``partitioned``: the Leis-style preagg/exchange/final
    pipeline (core/partitioned.py) runs per chunk — each chunk IS a morsel
    batch through local pre-aggregation — and the chunk's partial groups
    merge into the carried table.  One aggregate per plan (the pre-agg
    table carries a single partial)."""

    strategy_label = "partitioned"

    def __init__(self, plan: GroupByPlan):
        super().__init__(plan)
        self._agg = _single_agg(plan, "partitioned")

    def _chunk_partial(self, keys, vals):
        from repro.core.partitioned import _partitioned_impl

        p, ex = self._plan, self._plan.execution
        v = (vals[self._agg.column] if self._agg.column
             else jnp.ones(keys.shape, jnp.float32))
        rem = (-int(keys.shape[0])) % ex.num_workers
        if rem:
            keys = jnp.concatenate([keys, jnp.full((rem,), EMPTY_KEY, jnp.uint32)])
            v = jnp.concatenate([v, jnp.zeros((rem,), jnp.float32)])
        bound = self._chunk_bound
        while True:
            res = _partitioned_impl(
                keys, v, kind=self._agg.kind, max_groups=bound,
                num_workers=ex.num_workers, preagg_capacity=ex.preagg_capacity,
                morsel_size=ex.preagg_morsel,
            )
            ovf = res.num_groups > bound
            if p.saturation != SaturationPolicy.GROW:
                break
            issued = int(jax.device_get(res.num_groups))
            if issued <= bound:
                break
            if bound >= max(self._rows, issued):
                raise _overflow_error(issued, bound)
            bound = _next_bound(bound, self._rows, issued=issued)
        self._chunk_bound = bound
        spec = self._specs[0]
        return res.keys, {spec: res.values}, res.num_groups, ovf


# ---------------------------------------------------------------------------
# sharded: mesh-level execution


class _ShardedExecutor(_ExecutorBase):
    """Strategy ``sharded``, streaming ingest: the paper's thread-local
    method made incremental at mesh scale.  Every chunk is ``shard_map``'d
    over the mesh and folded into per-device carried state (local ticket
    table + dense partial vector — ``core.distributed.ShardedCarry``); the
    cross-device merge runs ONCE at finalize:

      * ``shard_merge="dense_psum"`` — all-gather unique keys, union-build
        the global table, one dense psum (the thread-local merge);
      * ``"all_to_all"`` — exchange the per-device LOCAL AGGREGATES by key
        partition, owners finish alone (the Leis baseline, its exchange now
        over O(cardinality) state instead of buffered rows).

    Device state is O(devices × capacity), independent of stream length —
    no chunk is ever buffered.  Under GROW, consume runs the checked step:
    devices pause in-scan before their bound/load-factor is crossed and the
    host widens EVERY device's table (vmapped §4.4 migrate) and resumes
    each device at its own paused morsel — the mesh analogue of the
    operator's pause/migrate/resume, closing the "sharded saturation
    re-runs the whole exchange" gap.  RAISE/UNCHECKED run the zero-sync
    step; RAISE reads the sticky per-device loss flags once at finalize.

    Single-chunk consumes keep the caller's device sharding (the legacy
    adapters); after ``finalize`` the strategy's raw mesh output is kept on
    ``.raw`` for callers that need the per-device layout.
    """

    strategy_label = "sharded"

    def __init__(self, plan: GroupByPlan):
        self._plan = plan
        self._specs = expand_agg_specs(plan.aggs)
        self._vcols = tuple(sorted({c for c, _ in self._specs if c is not None}))
        ex = plan.execution
        if ex.mesh is None:
            raise ValueError("strategy 'sharded' requires ExecutionPolicy.mesh")
        if ex.shard_merge not in ("dense_psum", "all_to_all"):
            raise ValueError(f"unknown shard_merge {ex.shard_merge!r}")
        self._ndev = ex.mesh.shape[ex.axis]
        self._max_local = ex.max_local_groups or plan.max_groups
        self._max_groups = plan.max_groups
        self._checked = plan.saturation == SaturationPolicy.GROW
        self._collect = _instrument(plan)
        self._events = None
        self.migrations = 0
        self.bound_grows = 0
        self.remeshes = 0
        self._carry = None
        self._step = None
        self._rows = 0
        self.raw = None
        self._merged = None

    @property
    def mesh(self):
        return self._plan.execution.mesh

    def remesh(self, mesh, *, axis: str | None = None) -> None:
        """Move the stream onto a DIFFERENT mesh at a chunk boundary — the
        elastic device-loss recovery (engine/elastic.py drives it).  The
        carried per-device state re-buckets onto the new device count
        (``core.distributed.rebucket_sharded_carry``: the same all_to_all
        key-partition rule as the exchange merge, duplicate keys folded with
        their merge kind), the consume step recompiles for the new mesh
        lazily, and consumption resumes exactly where it paused — results
        stay bit-exact because every merge in the pipeline is key-wise.

        The caller owns the chunk boundary: any in-flight ``consume_async``
        tokens must be polled first (``StreamHandle`` drains them before a
        re-mesh or a save)."""
        from repro.core import distributed as dist

        ex = self._plan.execution
        axis = axis or ex.axis
        new_ndev = mesh.shape[axis]
        with obs_trace.span(
            "remesh", strategy="sharded", old_ndev=self._ndev,
            new_ndev=new_ndev,
        ):
            if self._carry is not None:
                self._carry, self._max_local = dist.rebucket_sharded_carry(
                    self._carry, new_ndev,
                    load_factor=ex.load_factor, max_local=self._max_local,
                )
            if self._events is not None:
                # keep event TOTALS: park the old planes' sum on device 0 of
                # the survivor mesh (event_counts sums over devices anyway)
                total = np.asarray(jax.device_get(self._events)).sum(axis=0)
                self._events = (
                    jnp.zeros((new_ndev, obs_metrics.EVENT_VEC_LEN), jnp.int32)
                    .at[0].set(jnp.asarray(total, jnp.int32))
                )
            self._plan = replace(
                self._plan, execution=replace(ex, mesh=mesh, axis=axis)
            )
            self._ndev = new_ndev
            self._step = None  # recompiles for the new mesh on next consume
            self.remeshes += 1
        if obs_metrics.enabled():
            obs_metrics.counter(
                "elastic.remesh", strategy=self.strategy_label
            ).add(1)

    def _ensure_state(self):
        from repro.core import distributed as dist

        ex = self._plan.execution
        if self._carry is None:
            self._carry = dist.make_sharded_carry(
                self._ndev, self._max_local, self._specs,
                capacity=table_capacity(self._max_local, ex.load_factor),
            )
        if self._collect and self._events is None:
            self._events = jnp.zeros(
                (self._ndev, obs_metrics.EVENT_VEC_LEN), jnp.int32
            )
        if self._step is None:
            self._step = dist.make_sharded_consume_step(
                ex.mesh, ex.axis,
                update=ex.update or "scatter", load_factor=ex.load_factor,
                checked=self._checked, collect_events=self._collect,
            )

    def _run_step(self, km, vm, start):
        """One sharded consume step, threading the per-device event planes
        when instrumented.  Returns the per-device halt flags."""
        if self._collect:
            self._carry, halts, self._events = self._step(
                self._carry, km, vm, start, self._events
            )
        else:
            self._carry, halts = self._step(self._carry, km, vm, start)
        return halts

    def _morselize(self, keys, vals):
        """Split a chunk's rows contiguously over the mesh axis and each
        device's slice into morsels: keys (ndev, num_morsels, morsel_rows)
        plus one value plane per aggregated column (padding rows carry
        EMPTY_KEY, so their zero values park in ``updates._masked``)."""
        ex = self._plan.execution
        n = int(keys.shape[0])
        per_dev = -(-n // self._ndev)
        m = max(min(ex.morsel_rows, per_dev), 1)
        per_dev = -(-per_dev // m) * m
        total = per_dev * self._ndev
        if total > n:
            keys = jnp.concatenate(
                [keys, jnp.full((total - n,), EMPTY_KEY, jnp.uint32)]
            )
            vals = {
                c: jnp.concatenate([v, jnp.zeros((total - n,), jnp.float32)])
                for c, v in vals.items()
            }
        return (
            keys.reshape(self._ndev, per_dev // m, m),
            {c: v.reshape(self._ndev, per_dev // m, m) for c, v in vals.items()},
        )

    def consume(self, chunk: Table) -> None:
        self.poll(self.consume_async(chunk))

    def consume_async(self, chunk: Table):
        keys, vals = _chunk_keys_values(self._plan, chunk)
        vals = {c: vals[c] for c in self._vcols}
        self._rows += int(keys.shape[0])
        self._ensure_state()
        km, vm = self._morselize(keys, vals)
        start = jnp.zeros((self._ndev,), jnp.int32)
        halts = self._run_step(km, vm, start)
        return (km, vm, halts) if self._checked else None

    def poll(self, token) -> None:
        from repro.core import distributed as dist

        if token is None:
            return
        km, vm, halts = token
        ex = self._plan.execution
        m = km.shape[2]
        nm = km.shape[1]
        replayed = None
        while True:
            halts_np = np.asarray(jax.device_get(halts))  # (ndev, nm)
            firsts = [
                int(np.flatnonzero(halts_np[d])[0]) if halts_np[d].any() else nm
                for d in range(self._ndev)
            ]
            if all(f == nm for f in firsts):
                return
            counts = np.asarray(jax.device_get(self._carry.count))
            top = int(counts.max())
            new_maxl, new_cap = self._max_local, self._carry.capacity
            if top > self._max_local - m:
                new_maxl = max(4 * self._max_local, top + m, 64)
            if top > ex.load_factor * self._carry.capacity:
                new_cap = 2 * self._carry.capacity
            new_cap = max(new_cap, table_capacity(new_maxl, ex.load_factor))
            if (new_maxl, new_cap) == (self._max_local, self._carry.capacity):
                if firsts == replayed:
                    # pause survived an ungrown replay: force progress
                    new_cap = 2 * self._carry.capacity
                # else: an earlier token's poll already grew — just replay
            if (new_maxl, new_cap) != (self._max_local, self._carry.capacity):
                with obs_trace.span(
                    "pause_migrate_resume", strategy="sharded",
                    max_local=new_maxl, capacity=new_cap,
                ):
                    if new_cap != self._carry.capacity:
                        self.migrations += 1  # every device's table migrates
                    if new_maxl != self._max_local:
                        self.bound_grows += 1
                    self._carry = dist.grow_sharded_carry(
                        self._carry, new_maxl, new_cap
                    )
                    self._max_local = new_maxl
            replayed = firsts
            start = jnp.asarray(firsts, jnp.int32)
            halts = self._run_step(km, vm, start)

    def finalize_raw(self):
        """Run the cross-device merge under the saturation policy over the
        carried state and return the strategy's native output (sets
        ``.raw``), skipping the unified-table compaction — the legacy
        per-device adapters need only this.  Pure in the carry: mid-stream
        snapshots merge, read, and keep consuming.

        Returns ``(max_groups, count)`` alongside setting ``self.raw``.
        """
        from repro.core import distributed as dist

        if self._carry is None:
            raise ValueError("GroupByPlan executed over zero chunks")
        p, ex = self._plan, self._plan.execution
        max_groups = self._max_groups
        if ex.shard_merge == "dense_psum":
            from repro.core.aggregation import GroupByResult

            while True:
                kbt, gstate, count, lovf, union_ovf = dist.sharded_psum_merge(
                    ex.mesh, ex.axis, self._carry, max_groups=max_groups,
                )
                self._merged = (kbt, gstate, count)
                spec = self._specs[0]
                # legacy per-device view: single-spec plans keep the
                # GroupByResult raw layout the adapters/tests read
                self.raw = GroupByResult(
                    kbt, up.finalize(spec[1], gstate.accs[0]), count,
                ) if len(self._specs) == 1 else (kbt, gstate, count)
                if p.saturation == SaturationPolicy.UNCHECKED:
                    return max_groups, count
                lost, uovf, issued = (int(x) for x in jax.device_get(
                    (lovf, union_ovf, count)
                ))
                if lost > 0:
                    # keys dropped at a device BEFORE the union — only
                    # reachable under RAISE (GROW's checked consume pauses
                    # instead of dropping)
                    raise GroupByOverflowError(
                        "sharded GROUP BY overflow: a per-device table "
                        f"exceeded its local bound ({self._max_local}); "
                        "dropped keys never reach the merge. Use "
                        "SaturationPolicy.GROW or larger bounds."
                    )
                if uovf == 0 and issued <= max_groups:
                    self._max_groups = max_groups
                    return max_groups, count
                if p.saturation == SaturationPolicy.RAISE or max_groups >= self._rows:
                    raise _overflow_error(issued, max_groups)
                # GROW at the union: re-merge over the carried state with a
                # wider global bound — cheap, no rows involved
                max_groups = _next_bound(
                    max_groups, self._rows,
                    issued=issued if issued > max_groups else None,
                )
        else:
            pc = ex.partition_capacity
            while True:
                keys_p, vals_p, counts_p, overflow_p, lovf = (
                    dist.sharded_exchange_merge(
                        ex.mesh, ex.axis, self._carry,
                        max_groups=max_groups, partition_capacity=pc,
                    )
                )
                self._merged = (keys_p, vals_p, counts_p)
                # legacy per-device view: single-spec plans keep the flat
                # finalized vals vector the adapters/tests read
                legacy_vals = (
                    up.finalize(self._specs[0][1], vals_p[0])
                    if len(self._specs) == 1 else vals_p
                )
                self.raw = (keys_p, legacy_vals, counts_p, overflow_p)
                count = jnp.sum(counts_p)
                if p.saturation == SaturationPolicy.UNCHECKED:
                    return max_groups, count
                lost, bucket_ovf, issued = (int(x) for x in jax.device_get(
                    (lovf, jnp.sum(overflow_p), count)
                ))
                if lost > 0:
                    raise GroupByOverflowError(
                        "sharded GROUP BY overflow: a per-device table "
                        f"exceeded its local bound ({self._max_local}); "
                        "dropped entries never reach the exchange. Use "
                        "SaturationPolicy.GROW or larger bounds."
                    )
                if bucket_ovf > 0:
                    # GROW: double the per-partition bucket capacity and
                    # re-run the exchange over the carried state.  One
                    # source device can send a partition at most its whole
                    # local table, so max_local bounds the doubling.
                    base = pc or max(2 * self._max_local // self._ndev, 16)
                    if (p.saturation != SaturationPolicy.GROW
                            or base >= self._max_local):
                        raise GroupByOverflowError(
                            "partitioned exchange dropped entries (partition "
                            "bucket overflow); raise ExecutionPolicy."
                            "partition_capacity or use SaturationPolicy.GROW"
                        )
                    pc = min(2 * base, self._max_local)
                    continue
                if issued <= max_groups:
                    self._max_groups = max_groups
                    return max_groups, count
                if p.saturation == SaturationPolicy.RAISE or max_groups >= self._rows:
                    raise _overflow_error(issued, max_groups)
                max_groups = _next_bound(max_groups, self._rows, issued=issued)

    def finalize(self) -> Table:
        max_groups, count = self.finalize_raw()
        if self._plan.execution.shard_merge == "dense_psum":
            kbt, gstate, _ = self._merged
            get = gstate.get
        else:
            # Unify the per-partition outputs: stable compaction of each
            # owner's valid prefix (partitions are disjoint, so the keys
            # are globally unique).  Pure jnp over a replicated copy — no
            # host round-trip.
            from repro.core.distributed import replicated

            keys_p, vals_p, counts_p = replicated(self._merged)
            ndev = self._ndev
            per_dev = keys_p.shape[0] // ndev
            idx = jnp.arange(keys_p.shape[0])
            valid = (idx % per_dev) < jnp.take(counts_p.reshape(-1), idx // per_dev)
            order = jnp.argsort(~valid, stable=True)
            kbt = jnp.take(keys_p.reshape(-1), order)[:max_groups]
            accs = {
                spec: jnp.take(v.reshape(-1), order)[:max_groups]
                for spec, v in zip(self._specs, vals_p)
            }
            get = lambda c, k: accs[(c, k)]
        return build_result_table(
            self._plan.aggs, get, kbt, count, max_groups,
        )

    def device_table_bytes(self) -> int:
        if self._carry is None:
            return 0
        return sum(
            int(leaf.nbytes) for leaf in jax.tree_util.tree_leaves(self._carry)
        )

    def local_group_counts(self) -> list[int]:
        """Groups each device holds before the merge, in mesh order (one
        host read)."""
        if self._carry is None:
            return [0] * self._ndev
        return [int(c) for c in np.asarray(jax.device_get(self._carry.count))]

    def event_counts(self):
        if not self._collect or self._events is None:
            return None
        # one host round-trip, at an existing sync surface (stats/finalize);
        # per-device planes sum into one engine-wide vector
        ev, counts = jax.device_get((self._events, self._carry.count))
        out = obs_metrics.event_vector_to_dict(ev.sum(axis=0))
        out["migrations"] = self.migrations
        out["bound_grows"] = self.bound_grows
        out["remeshes"] = self.remeshes
        out["num_groups"] = int(counts.sum())  # pre-merge local groups
        out["table_capacity"] = int(self._carry.capacity) * self._ndev
        out["table_load_factor"] = float(counts.sum()) / (
            self._carry.capacity * self._ndev
        )
        return out


__all__ = [
    "batch_signature",
    "consume_batched",
    "make_executor",
    "resolve_plan",
    "resolve_plan_stats",
]
