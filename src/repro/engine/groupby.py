"""The GROUP BY operator: scan-compiled, morsel-driven, strategy-pluggable.

This is the operator a query plan instantiates.  It supports:
  * multiple aggregates per query (SUM/COUNT/MIN/MAX/MEAN over value cols),
  * multi-column grouping keys (hash-combined),
  * strategy selection — explicit or adaptive (core/adaptive.py),
  * a resize path when the cardinality estimate was wrong (core/resize.py),
  * single-core (pure-jnp or Pallas-kernel) and mesh-distributed execution.

Scan-compiled contract
----------------------
``consume`` is ONE jitted ``jax.lax.scan`` over the chunk's morsel axis,
threading ``(TicketTable, AggState)`` as the carry — probe, claim, ticket,
update all trace into a single compiled program, so per-morsel dispatch cost
is zero and the hot loop stays device-resident (the paper's premise that the
GROUP BY inner loop must be contention- and overhead-free).  The Pallas
kernel route is just another scan body: ``use_kernel=True`` swaps the update
stage for the VMEM segment-update kernel (kernels/ops.make_scan_update_fn).

Resizing follows the paper's §4.4 "pause, migrate, resume" with the pause
hoisted out of the hot loop: instead of a blocking ``int(table.count)`` host
sync before every morsel, the scan itself checks the load factor before each
morsel and *pauses* (subsequent morsels become no-ops) the moment growth is
needed, recording the pause index in its per-morsel halt flags.  A thin host
wrapper reads the flags once per chunk, migrates via ``resize.migrate``
(tickets survive, so ticket-indexed accumulators are untouched), and replays
only the affected suffix by re-entering the same compiled scan at the paused
morsel.  A morsel that saturates the probe table mid-stream does not commit
its accumulator updates and pauses the same way; replay after growth is
exact because published inserts are idempotent (the retry takes the
fast-path lookup and issues no new ticket).

The operator conforms to the morsel-driven contract: it consumes morsels
incrementally (``consume``) and produces its result only at ``finalize`` —
i.e. it is a pipeline breaker exactly like the paper's (and every) hash
aggregation.  ``finalize`` raises if the stream's distinct keys overflowed
``max_groups`` (truncated output would be silent data loss).

``pipeline="host"`` keeps the legacy per-morsel Python loop (one eager
dispatch + one blocking resize check per morsel) as the reference
implementation for A/B equivalence tests and the pipeline benchmark.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import resize
from repro.core import ticketing as tk
from repro.core import updates as up
from repro.core.hashing import EMPTY_KEY, table_capacity
from repro.engine.columns import Table, chunk_key_column
from repro.engine.morsels import DEFAULT_MORSEL_ROWS, morselize_chunk
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace


class GroupByOverflowError(RuntimeError):
    """The stream held more distinct keys than ``max_groups``."""


@dataclass(frozen=True)
class AggSpec:
    kind: str        # sum | count | min | max | mean
    column: str | None = None  # None for count

    @property
    def name(self) -> str:
        return f"{self.kind}({self.column or '*'})"


def build_result_table(aggs, get_acc, key_by_ticket, count, max_groups) -> Table:
    """THE uniform GROUP BY result layout, shared by the engine operator and
    every executor strategy: keys in ticket order, one materialized column
    per aggregate (mean composed from sum/count, min/max identities → NaN),
    and the broadcast group count."""
    n = key_by_ticket.shape[0]
    if n < max_groups:
        pad = jnp.full((max_groups - n,), EMPTY_KEY, jnp.uint32)
        key_by_ticket = jnp.concatenate([key_by_ticket.astype(jnp.uint32), pad])
    out = {"key": key_by_ticket[:max_groups]}
    for a in aggs:
        if a.kind == "mean":
            out[a.name] = up.finalize(
                "mean", get_acc(a.column, "sum"), get_acc(a.column, "count")
            )
        else:
            out[a.name] = up.finalize(a.kind, get_acc(a.column, a.kind))
    count = jnp.asarray(count, jnp.int32).reshape(())
    out["__num_groups__"] = jnp.broadcast_to(count, (max_groups,))
    return Table(out)


def expand_agg_specs(aggs: Sequence[AggSpec]) -> tuple:
    """Deduplicated ``(column, kind)`` accumulator specs for a query's aggs
    (``mean`` decomposes into sum+count, composed back at materialization)."""
    specs = []
    for a in aggs:
        kinds = ("sum", "count") if a.kind == "mean" else (a.kind,)
        for k in kinds:
            specs.append((a.column, k))
    return tuple(dict.fromkeys(specs))


def accumulate_scan_events(events, mkeys, probe_len, commit, pause_sat, halt_now):
    """Fold one morsel's device-side event counts into the int32 event vector
    (layout: ``obs.metrics`` EVT_* slots + probe-length histogram buckets).

    Committed-only semantics: row/probe counts accrue only when ``commit`` is
    true, so a pausing morsel's counts are dropped exactly like its state
    update and the post-migration replay counts it once.  ``pause_sat`` /
    ``halt_now`` count the pause events themselves (these DO fire on the
    non-committing morsel — that is the point)."""
    c = commit.astype(jnp.int32)
    valid = mkeys != jnp.uint32(EMPTY_KEY)
    n_valid = jnp.sum(valid.astype(jnp.int32))
    n_rows = jnp.int32(mkeys.shape[0])
    events = events.at[obs_metrics.EVT_MORSELS].add(c)
    events = events.at[obs_metrics.EVT_ROWS].add(c * n_valid)
    events = events.at[obs_metrics.EVT_ROWS_MASKED].add(c * (n_rows - n_valid))
    events = events.at[obs_metrics.EVT_PROBE_STEPS].add(c * jnp.sum(probe_len))
    events = events.at[obs_metrics.EVT_PROBE_SATURATIONS].add(
        pause_sat.astype(jnp.int32)
    )
    events = events.at[obs_metrics.EVT_PAUSES].add(halt_now.astype(jnp.int32))
    # Probe-length histogram: committed valid lanes only; everyone else parks
    # on an out-of-bounds index (mode="drop" no-op, the scatter idiom used by
    # ticketing itself).
    edges = jnp.asarray(obs_metrics.PROBE_HIST_EDGES, jnp.int32)
    bucket = jnp.searchsorted(edges, probe_len, side="right").astype(jnp.int32)
    idx = jnp.where(
        valid & commit,
        jnp.int32(obs_metrics.NUM_EVENTS) + bucket,
        jnp.int32(obs_metrics.EVENT_VEC_LEN),
    )
    return events.at[idx].add(1, mode="drop")


def make_pause_scan_body(start, threshold, bound_slack, apply_update,
                         count_events=False):
    """THE checked pause/commit morsel body, shared by the single-device
    consume scan below and the per-device mesh consume step
    (``core.distributed.make_sharded_consume_step``) so the §4.4 pause
    protocol lives in exactly one place.

    Invariant every caller depends on (deferred-poll safety, grow without
    replay): **a pausing morsel commits nothing** — the pre-morsel room
    check (load-factor threshold, plus bound headroom when ``bound_slack``
    is not None) halts BEFORE ticketing, and a morsel that saturates the
    probe table mid-flight has its state update dropped (published inserts
    are idempotent under replay).  ``apply_update(state, tickets, vals)``
    folds one ticketed morsel into the caller's accumulator pytree (a full
    ``AggState`` for the engine, a single dense vector per device on the
    mesh).

    ``count_events=True`` widens the carry to ``(table, state, halted,
    events)`` where ``events`` is the int32 vector of ``obs.metrics`` event
    counters (+ probe-length histogram), accumulated in-scan with
    committed-only semantics — see :func:`accumulate_scan_events`.  The
    default ``False`` path traces exactly as before."""

    def body(carry, xs):
        if count_events:
            table, state, halted, events = carry
        else:
            table, state, halted = carry
        idx, keys, vals = xs
        wants = idx >= start
        needs_room = table.count > threshold
        if bound_slack is not None:
            needs_room = needs_room | (table.count > bound_slack)
        halt_grow = wants & ~halted & needs_room
        halted = halted | halt_grow
        live = wants & ~halted
        mkeys = jnp.where(live, keys, jnp.uint32(EMPTY_KEY))
        if count_events:
            tickets, table, probe_len = tk.get_or_insert(
                table, mkeys, count_probes=True
            )
        else:
            tickets, table = tk.get_or_insert(table, mkeys)
        # Saturation: a valid row came back unticketed (no reachable empty
        # slot).  The morsel does not commit — its published inserts are
        # idempotent under replay, and its updates are dropped below.
        sat = jnp.any((tickets < 0) & (mkeys != jnp.uint32(EMPTY_KEY)))
        new_state = apply_update(state, tickets, vals)
        commit = live & ~sat
        state = jax.tree_util.tree_map(
            lambda new, old: jnp.where(commit, new, old), new_state, state
        )
        halt_now = halt_grow | (live & sat)
        halted = halted | halt_now
        if count_events:
            events = accumulate_scan_events(
                events, mkeys, probe_len, commit, live & sat, halt_now
            )
            return (table, state, halted, events), halt_now
        return (table, state, halted), halt_now

    return body


@functools.partial(
    jax.jit,
    static_argnames=("update_fn", "load_factor", "checked", "grow_bound",
                     "collect_events"),
)
def _consume_scan(table, state, km, vm, start, events=None, *, update_fn,
                  load_factor, checked=True, grow_bound=False,
                  collect_events=False):
    """One fused pass over a chunk's morsels: scan (probe→ticket→update).

    Morsels with index < ``start`` are skipped (resume support).  Before each
    morsel the body checks the growth condition; at the first morsel that
    needs growth (load factor crossed) or fails to fully ticket (probe table
    saturated), the scan pauses: that morsel and everything after become
    no-ops and its index is flagged in the returned per-morsel ``halts``.

    ``grow_bound=True`` additionally pauses when the NEXT morsel could issue
    tickets past ``max_groups`` (count > max_groups - morsel_rows): the
    pause fires before anything is dropped, so the host can widen the bound
    (``resize.grow_bound`` + ``updates.grow_agg_state``) and resume — bound
    misestimates recover in-stream with no chunk replay.

    ``checked=False`` is the paper's perfect-estimate regime: no growth or
    saturation checks trace at all — the table never migrates, every morsel
    commits, rows that fail to ticket (ticket -1) are parked by the update
    masks, and the returned ``halts`` are constant-false so the host never
    needs to read them (zero blocking syncs).

    ``collect_events=True`` threads the caller's ``events`` vector (see
    ``obs.metrics``) through the scan carry and returns it as a fourth
    output, accumulated entirely on device — the host reads it back only at
    sync points it already owns (finalize / explicit ``event_counts()``), so
    instrumentation adds zero extra device syncs.  With the default
    ``collect_events=False`` and ``events=None`` the traced program is
    byte-identical to the uninstrumented one.
    """
    capacity = table.capacity
    threshold = int(load_factor * capacity)
    # Static headroom: pause while there is still room for a full morsel.
    bound_slack = table.max_groups - km.shape[1]

    if checked:
        body = make_pause_scan_body(
            start, threshold, bound_slack if grow_bound else None,
            lambda s, t, v: up.update_agg_state(s, t, v, update_fn),
            count_events=collect_events,
        )
    else:
        def body(carry, xs):
            if collect_events:
                table, state, halted, events = carry
            else:
                table, state, halted = carry
            idx, keys, vals = xs
            wants = idx >= start
            mkeys = jnp.where(wants, keys, jnp.uint32(EMPTY_KEY))
            if collect_events:
                tickets, table, probe_len = tk.get_or_insert(
                    table, mkeys, count_probes=True
                )
            else:
                tickets, table = tk.get_or_insert(table, mkeys)
            new_state = up.update_agg_state(state, tickets, vals, update_fn)
            state = jax.tree_util.tree_map(
                lambda new, old: jnp.where(wants, new, old), new_state, state
            )
            if collect_events:
                # Unchecked: every wanted morsel commits; a saturated probe
                # table silently parks rows, so count it as a saturation
                # event (there is no pause to count).
                sat = wants & jnp.any(
                    (tickets < 0) & (mkeys != jnp.uint32(EMPTY_KEY))
                )
                events = accumulate_scan_events(
                    events, mkeys, probe_len, wants, sat, jnp.zeros((), jnp.bool_)
                )
                return (table, state, halted, events), jnp.zeros((), jnp.bool_)
            return (table, state, halted), jnp.zeros((), jnp.bool_)

    idxs = jnp.arange(km.shape[0], dtype=jnp.int32)
    if collect_events:
        (table, state, _, events), halts = jax.lax.scan(
            body, (table, state, jnp.zeros((), jnp.bool_), events), (idxs, km, vm)
        )
        return table, state, halts, events
    (table, state, _), halts = jax.lax.scan(
        body, (table, state, jnp.zeros((), jnp.bool_)), (idxs, km, vm)
    )
    return table, state, halts


@dataclass
class GroupByOperator:
    key_columns: Sequence[str]
    aggs: Sequence[AggSpec]
    max_groups: int
    morsel_rows: int = DEFAULT_MORSEL_ROWS
    update: str = "scatter"
    use_kernel: bool = False          # route updates through the Pallas kernels
    load_factor: float = 0.5
    pipeline: str = "scan"            # scan (compiled) | host (reference loop)
    capacity: int | None = None       # probe-table slots; None → table_capacity
    raw_keys: bool = False            # single pre-hashed uint32 key column
    check_overflow: bool = True       # False = paper's perfect-estimate regime
    grow_bound: bool = False          # widen max_groups in-stream (no replay)
    collect_events: bool = False      # thread the obs event vector in-scan

    def __post_init__(self):
        cap = self.capacity or table_capacity(self.max_groups, self.load_factor)
        self._table = tk.make_table(cap, max_groups=self.max_groups)
        if self.raw_keys:
            assert len(self.key_columns) == 1, "raw_keys needs exactly one key column"
        self._state = up.init_agg_state(expand_agg_specs(self.aggs), self.max_groups)
        if self.use_kernel:
            from repro.kernels import ops as kops

            strategy = self.update if self.update in ("scatter", "onehot") else "scatter"
            self._update_fn = kops.make_scan_update_fn(strategy=strategy)
        else:
            self._update_fn = up.get_update_fn(self.update)
        self._overflowed = False  # host mirror of table.overflowed
        # Device event vector (None = uninstrumented trace, byte-identical to
        # pre-obs) + host-side growth counters (plain ints, always cheap).
        self._events = (
            obs_metrics.zero_event_vector() if self.collect_events else None
        )
        self.migrations = 0
        self.bound_grows = 0
        assert self.pipeline in ("scan", "host"), self.pipeline

    # -- morsel-driven contract ---------------------------------------------
    def consume(self, chunk: Table) -> None:
        """Consume one pipeline chunk (any row count; morselized here).

        An optional boolean ``__mask__`` column marks filtered-out rows
        (selection-vector idiom): their combined key becomes the EMPTY
        sentinel, which ticketing skips.
        """
        self.poll(self.consume_async(chunk))

    def consume_async(self, chunk: Table):
        """Dispatch one chunk's consume scan WITHOUT blocking on its control
        signals.  Returns an opaque in-flight token that MUST later be
        handed to :meth:`poll` (in dispatch order); ``None`` means there is
        nothing to poll (host pipeline, unchecked regime, poisoned stream).

        This is the double-buffered ingest seam: while the device runs the
        dispatched scan, the host is free to stage (morselize) the next
        chunk.  Deferring ``poll`` is safe because a chunk that pauses
        commits nothing from the paused morsel onward, and every subsequent
        chunk's scan re-evaluates the same pause condition at its first
        morsel — so later in-flight chunks no-op until the host catches up,
        and replay happens in chunk order when their tokens are polled.
        """
        if self._overflowed and self.check_overflow:
            return None  # poisoned: skip the scan, finalize raises anyway
        with obs_trace.span("combine_keys"):
            keys, cols = chunk_key_column(chunk, self.key_columns, self.raw_keys)
        value_cols = sorted({c for c, _ in self._state.specs if c is not None})
        with obs_trace.span("morselize"):
            km, vm, num = morselize_chunk(
                keys, {c: cols[c] for c in value_cols}, self.morsel_rows
            )
        if self.pipeline == "host":
            self._consume_host_loop(km, vm, num)
            return None
        if not self.check_overflow:
            # Perfect-estimate regime (unchecked): one pass, fixed capacity,
            # no migrations and NO blocking sync — rows past the bound (or a
            # saturated probe table) drop, exactly the legacy jitted paths.
            self._run_scan(km, vm, 0, checked=False)
            return None
        halts = self._run_scan(km, vm, 0)
        return (km, vm, halts, self._table.overflowed)

    def _run_scan(self, km, vm, start, *, checked=True):
        """Dispatch one ``_consume_scan`` pass, threading the device event
        vector through the carry when instrumented.  Returns the per-morsel
        halt flags (constant-false unchecked)."""
        with obs_trace.span("dispatch"):
            if self.collect_events:
                self._table, self._state, halts, self._events = _consume_scan(
                    self._table, self._state, km, vm, jnp.int32(start),
                    self._events, update_fn=self._update_fn,
                    load_factor=self.load_factor, checked=checked,
                    grow_bound=checked and self.grow_bound, collect_events=True,
                )
            else:
                self._table, self._state, halts = _consume_scan(
                    self._table, self._state, km, vm, jnp.int32(start),
                    update_fn=self._update_fn, load_factor=self.load_factor,
                    checked=checked, grow_bound=checked and self.grow_bound,
                )
        return halts

    def poll(self, token) -> None:
        """Resolve one in-flight chunk: read its control signals (ONE
        blocking device round-trip) and run pause → migrate/grow → resume
        until the chunk is fully consumed."""
        if token is None:
            return
        km, vm, halts, overflowed = token
        replayed = -1  # morsel we already optimistically replayed ungrown
        while True:
            overflowed_np, halts_np = jax.device_get((overflowed, halts))
            if bool(overflowed_np):
                self._overflowed = True
                return  # poisoned: finalize raises instead of truncating
            flagged = np.flatnonzero(halts_np)
            if flagged.size == 0:
                return
            # Pause → migrate/grow → resume (§4.4).  One device round-trip
            # per growth event instead of one per morsel; accumulators are
            # ticket-indexed so capacity migration never touches them.
            start = int(flagged[0])
            with obs_trace.span("pause_migrate_resume", morsel=start):
                if not self._grow(km.shape[1]) and start == replayed:
                    # The pause survived a replay with no growth condition
                    # met (an earlier in-flight chunk's poll already grew,
                    # or a boundary-saturated probe cluster): force a
                    # doubling so the replay loop always makes progress.
                    self._table = resize.migrate(
                        self._table, 2 * self._table.capacity
                    )
                    self.migrations += 1
                replayed = start
                halts = self._run_scan(km, vm, start)
                overflowed = self._table.overflowed

    def _grow(self, morsel_rows: int) -> bool:
        """Host side of a pause: widen whatever the pause was about — the
        cardinality bound (``grow_bound`` headroom crossed), the probe
        capacity (load factor crossed), or both.  Returns False when neither
        condition holds against the CURRENT state (the pause may have been
        handled already by an earlier in-flight chunk's poll — deferred
        ingest re-checks instead of blindly growing)."""
        count = int(jax.device_get(self._table.count))
        grew = False
        cap_before = self._table.capacity
        if self.grow_bound and count > self.max_groups - morsel_rows:
            new_max = max(4 * self.max_groups, count + morsel_rows, 64)
            self._table = resize.grow_bound(self._table, new_max, self.load_factor)
            self._state = up.grow_agg_state(self._state, new_max)
            self.max_groups = new_max
            self.bound_grows += 1
            grew = True
        if count > self.load_factor * self._table.capacity:
            self._table = resize.migrate(self._table, 2 * self._table.capacity)
            grew = True
        if self._table.capacity != cap_before:
            self.migrations += 1  # bound grow may migrate internally, too
        return grew

    def _consume_host_loop(self, km, vm, num) -> None:
        """Reference pipeline (the pre-scan implementation): one eager Python
        iteration per morsel with a blocking host-side resize check.  With
        ``check_overflow=False`` the resize check and saturation replay are
        skipped so both pipelines share the unchecked contract (fixed
        capacity, rows past a saturated table drop)."""
        for i in range(num):
            if self.check_overflow:
                if self.grow_bound:
                    self._grow(km.shape[1])  # bound headroom + load factor
                else:
                    cap_before = self._table.capacity
                    self._table = resize.maybe_resize(self._table, self.load_factor)
                    if self._table.capacity != cap_before:
                        self.migrations += 1
            tickets, self._table = tk.get_or_insert(self._table, km[i])
            # Saturation recovery (bounded probe loop's ticket==-1 contract):
            # migrate and replay the morsel, same as the scan path's pause.
            while self.check_overflow and bool(
                jax.device_get(jnp.any((tickets < 0) & (km[i] != jnp.uint32(EMPTY_KEY))))
            ):
                self._table = resize.migrate(self._table, 2 * self._table.capacity)
                self.migrations += 1
                tickets, self._table = tk.get_or_insert(self._table, km[i])
            self._state = up.update_agg_state(
                self._state, tickets, {c: v[i] for c, v in vm.items()},
                self._update_fn,
            )

    def finalize(self) -> Table:
        """Materialize: keys in ticket order + one column per aggregate.

        Raises RuntimeError if the stream held more than ``max_groups``
        distinct keys — tickets past the bound had their key/accumulator
        scatters dropped, so a truncated result would be silent data loss.
        """
        if self.check_overflow and (
            self._overflowed or bool(jax.device_get(self._table.overflowed))
        ):
            raise GroupByOverflowError(
                f"GROUP BY overflow: {int(self._table.count)} distinct keys "
                f"exceed max_groups={self.max_groups}; groups past the bound "
                "were dropped. Re-run with a larger max_groups (or a better "
                "cardinality estimate)."
            )
        return build_result_table(
            self.aggs, self._state.get, self._table.key_by_ticket,
            self._table.count, self._table.max_groups,
        )

    @property
    def num_groups(self):
        return self._table.count

    def event_counts(self) -> dict:
        """Merged operator counters: the device event vector (ONE device
        round-trip — call only at finalize-grade sync points) + host-tracked
        growth events + table occupancy.  Zeros for the device half when the
        operator was built uninstrumented (``collect_events=False``)."""
        if self._events is not None:
            vec, count = jax.device_get((self._events, self._table.count))
            out = obs_metrics.event_vector_to_dict(vec)
        else:
            count = jax.device_get(self._table.count)
            out = {name: 0 for name in obs_metrics.EVENT_NAMES}
            out["probe_hist"] = [0] * obs_metrics.PROBE_HIST_BUCKETS
        out["migrations"] = self.migrations
        out["bound_grows"] = self.bound_grows
        out["num_groups"] = int(count)
        out["table_capacity"] = self._table.capacity
        out["table_load_factor"] = int(count) / self._table.capacity
        return out


def groupby(
    table: Table,
    keys: Sequence[str],
    aggs: Sequence[AggSpec],
    *,
    max_groups: int | None = None,
    update: str | None = None,
    morsel_rows: int = DEFAULT_MORSEL_ROWS,
    strategy: str = "auto",
    saturation: str | None = None,
) -> Table:
    """One-shot GROUP BY with adaptive strategy selection (paper's
    recommended optimizer integration: estimate → choose → run).

    Adapter over the :class:`~repro.engine.plan_api.GroupByPlan` front door:
    builds a plan (``strategy="auto"`` → sample stats → planner choice) and
    executes it.  ``saturation=None`` defers to the plan API's default:
    ``grow`` when ``max_groups`` is estimated (a sample cannot see a long
    tail, so the executor recovers instead of surfacing an error about a
    parameter nobody passed), ``raise`` for an explicit caller bound.
    """
    from repro.engine.plan_api import ExecutionPolicy, GroupByPlan, execute

    plan = GroupByPlan(
        keys=tuple(keys), aggs=tuple(aggs), strategy=strategy,
        max_groups=max_groups, saturation=saturation,
        execution=ExecutionPolicy(update=update, morsel_rows=morsel_rows),
    )
    return execute(plan, table)
