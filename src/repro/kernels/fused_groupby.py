"""Pallas TPU kernel: FUSED ticketing + aggregate update, table in VMEM.

The paper executes group aggregation "in a vectorized fashion: ticketing an
entire morsel, then aggregating that morsel" (§1).  The split kernels
(ticket_hash, segment_agg) realize that pipeline with the ticket vector
making a round trip through HBM between phases.  This kernel fuses both
phases in VMEM: a morsel's tickets never leave the core — the claim
protocol resolves them and the scatter-accumulate consumes them in the same
grid step.  Saves 4 B/row of HBM traffic and one kernel launch per morsel;
on the 819 GB/s v5e that is ~25 % of the pipeline's minimum traffic for
uint32 keys + f32 values.

This is the production fused route behind ``ExecutionPolicy.kernel="fused"``
(engine/executors.py `_FusedExecutor`), not a one-shot prototype:

* **Full AggState contract** — any number of sum/count/min/max partials
  (``mean`` arrives pre-decomposed into sum+count by
  ``engine.groupby.expand_agg_specs``) accumulate in one pass; ``specs``
  maps each accumulator row to its value plane.
* **Persistent, resumable state** — the table and accumulators ride
  constant-index blocks: carried IN as inputs (copied to the outputs at the
  program's first grid step), carried OUT for the next chunk, so the
  executor streams chunks through one VMEM-resident table exactly like the
  scan pipeline carries its :class:`~repro.core.ticketing.TicketTable`.
* **Two-level tables** — ``programs > 1`` gives every grid program its own
  local table/accumulator block over a contiguous slice of the morsels (the
  NUMA-local first level of Tripathy & Green's scalable hash table); the
  host-side :func:`merge_fused_state` performs the second-level merge into
  one global ticket space at the boundary.
* **Bounded claim loop + sticky flags** — the probe loop is bounded at
  ``2*capacity + 2`` rounds like the split ticket kernel (a saturated VMEM
  table halts via the sticky saturation flag instead of spinning forever
  inside the grid step), and the §4.4 pause protocol from
  ``engine.groupby.make_pause_scan_body`` is reproduced in-kernel: a morsel
  that would cross the load-factor threshold (or the bound headroom, under
  GROW) halts BEFORE ticketing and commits nothing; a mid-morsel saturated
  morsel keeps its idempotently published inserts but drops its accumulator
  updates.  The host grows/migrates (Maier et al.'s folklore-table growing,
  via ``core.resize``) and resumes at the first halted morsel.
* **Observability** — the same int32 device event vector as the scan route
  (``obs.metrics`` layout: committed morsels/rows, probe steps, probe-length
  histogram, saturation pauses), carried across launches, so
  ``stats()["repro.obs/v1"]`` is uniform across scan and fused routes.

Results leave through the existing ticket contract only at the boundary:
``key_by_ticket`` + raw accumulator arrays, which ``build_result_table``,
``snapshot()`` and the saturation policies consume unchanged.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import resize
from repro.core import ticketing as tk
from repro.core.hashing import table_capacity
from repro.kernels.ticket_hash import EMPTY_I32, _slot_hash_i32
from repro.obs import metrics as obs_metrics

_NEUTRAL = {"sum": 0.0, "count": 0.0, "min": float("inf"), "max": float("-inf")}

# Control-signal layout of the per-program SMEM info vector the kernel
# emits: issued-ticket count, first halted morsel (NO_HALT when the launch
# ran to completion), the sticky probe-saturation flag, and the live halted
# bit (kernel-internal, exposed for debugging).
INFO_COUNT = 0
INFO_FIRST_HALT = 1
INFO_SAT = 2
INFO_HALTED = 3
INFO_LEN = 4
NO_HALT = 0x7FFFFFFF


class FusedState(NamedTuple):
    """Carried device state of the fused route — one local table +
    accumulator block per grid program, plus the cumulative event vector.

    Attributes:
      tkeys:  (P, C) int32 — probe-table keys (EMPTY_I32 where unoccupied).
      ttks:   (P, C) int32 — 1-based tickets, 0 where unoccupied.
      kbt:    (P, G) int32 — keys in local ticket order.
      accs:   (S, P, G) f32 — one raw partial per expanded agg spec.
      count:  (P,) int32 — local tickets issued.
      events: (P, EVENT_VEC_LEN) int32 — obs event vector per program.
    """

    tkeys: jnp.ndarray
    ttks: jnp.ndarray
    kbt: jnp.ndarray
    accs: jnp.ndarray
    count: jnp.ndarray
    events: jnp.ndarray

    @property
    def programs(self) -> int:
        return self.tkeys.shape[0]

    @property
    def capacity(self) -> int:
        return self.tkeys.shape[1]

    @property
    def max_groups(self) -> int:
        return self.kbt.shape[1]

    def nbytes(self) -> int:
        return sum(int(a.nbytes) for a in self)


def init_fused_state(
    *, capacity: int, max_groups: int, kinds: tuple, programs: int = 1
) -> FusedState:
    """Fresh empty state for ``programs`` local tables of ``capacity`` slots
    and a ``max_groups`` per-program ticket bound, with one neutral-filled
    accumulator plane per agg kind."""
    assert capacity & (capacity - 1) == 0, "capacity must be a power of 2"
    accs = jnp.stack(
        [jnp.full((programs, max_groups), _NEUTRAL[k], jnp.float32) for k in kinds]
    )
    return FusedState(
        tkeys=jnp.full((programs, capacity), EMPTY_I32, jnp.int32),
        ttks=jnp.zeros((programs, capacity), jnp.int32),
        kbt=jnp.full((programs, max_groups), EMPTY_I32, jnp.int32),
        accs=accs,
        count=jnp.zeros((programs,), jnp.int32),
        events=jnp.zeros((programs, obs_metrics.EVENT_VEC_LEN), jnp.int32),
    )


def program_table(state: FusedState, p: int) -> tk.TicketTable:
    """View one program's local table as a :class:`core.ticketing.TicketTable`
    (the layouts match exactly — int32 sentinel is the uint32 EMPTY_KEY), so
    ``core.resize`` migration/growth and the second-level merge reuse the
    core machinery unchanged."""
    return tk.TicketTable(
        keys=state.tkeys[p].astype(jnp.uint32),
        tickets=state.ttks[p],
        key_by_ticket=state.kbt[p].astype(jnp.uint32),
        count=state.count[p],
        overflowed=state.count[p] > state.max_groups,
    )


def grow_fused_state(
    state: FusedState,
    kinds: tuple,
    *,
    new_max_groups: int | None = None,
    new_capacity: int | None = None,
    load_factor: float = 0.5,
) -> FusedState:
    """Host-side §4.4 growth at a chunk/pause boundary: widen every local
    table's bound via ``resize.grow_bound`` and/or migrate its probe slots
    via ``resize.migrate`` (tickets are immutable, so the key→ticket map is
    preserved exactly), padding the accumulator planes with per-kind
    neutral elements — the fused analogue of ``updates.grow_agg_state``."""
    tables = []
    for p in range(state.programs):
        t = program_table(state, p)
        t = t._replace(overflowed=jnp.zeros((), jnp.bool_))
        if new_max_groups is not None and new_max_groups > t.max_groups:
            t = resize.grow_bound(t, new_max_groups, load_factor)
        if new_capacity is not None and new_capacity > t.capacity:
            t = resize.migrate(t, new_capacity)
        tables.append(t)
    g_new = tables[0].max_groups
    accs = state.accs
    pad = g_new - state.max_groups
    if pad > 0:
        accs = jnp.concatenate(
            [
                accs,
                jnp.stack(
                    [
                        jnp.full((state.programs, pad), _NEUTRAL[k], jnp.float32)
                        for k in kinds
                    ]
                ),
            ],
            axis=2,
        )
    return FusedState(
        tkeys=jnp.stack([t.keys.astype(jnp.int32) for t in tables]),
        ttks=jnp.stack([t.tickets for t in tables]),
        kbt=jnp.stack([t.key_by_ticket.astype(jnp.int32) for t in tables]),
        accs=accs,
        count=state.count,
        events=state.events,
    )


def merge_fused_state(
    state: FusedState, kinds: tuple, *, max_groups: int | None = None,
    load_factor: float = 0.5,
):
    """Second-level merge: fold the P local (key_by_ticket, accs) partials
    into ONE global ticket space (Tripathy & Green's upper level).  Pure —
    safe to call repeatedly for ``snapshot()``.

    Returns ``(table, accs)`` where ``table`` is a global
    :class:`TicketTable` and ``accs`` is a list of (max_groups,) raw
    partials aligned with ``kinds``.  With a single program the local state
    IS the global state (no merge, native ticket order preserved)."""
    if max_groups is None:
        max_groups = state.max_groups
    if state.programs == 1 and max_groups == state.max_groups:
        return program_table(state, 0), [
            state.accs[s, 0] for s in range(len(kinds))
        ]
    table = tk.make_table(table_capacity(max_groups, load_factor), max_groups)
    accs = [jnp.full((max_groups,), _NEUTRAL[k], jnp.float32) for k in kinds]
    for p in range(state.programs):
        keys_p = state.kbt[p].astype(jnp.uint32)  # EMPTY past local count
        tickets, table = tk.get_or_insert(table, keys_p)
        idx = jnp.where(tickets >= 0, tickets, max_groups)  # park → drop
        for s, k in enumerate(kinds):
            vv = jnp.where(tickets >= 0, state.accs[s, p], _NEUTRAL[k])
            if k in ("sum", "count"):
                accs[s] = accs[s].at[idx].add(vv, mode="drop")
            elif k == "min":
                accs[s] = accs[s].at[idx].min(vv, mode="drop")
            else:
                accs[s] = accs[s].at[idx].max(vv, mode="drop")
    return table, accs


def _fused_kernel(
    start_ref,      # (1,) int32 SMEM — resume morsel for this program
    count_in_ref,   # (1,) int32 SMEM — carried ticket count
    keys_ref,       # (1, M) int32 — this grid step's morsel
    vals_ref,       # (V, 1, M) f32 — value planes for the morsel
    tkeys_in_ref,   # (1, C) int32 — carried probe keys
    ttks_in_ref,    # (1, C) int32 — carried probe tickets
    kbt_in_ref,     # (1, G) int32 — carried ticket-ordered keys
    accs_in_ref,    # (S, 1, G) f32 — carried accumulators
    events_in_ref,  # (1, EVENT_VEC_LEN) int32 — carried event vector
    tkeys_ref,      # persistent outputs (constant-index blocks per program)
    ttks_ref,
    kbt_ref,
    accs_ref,
    events_ref,
    info_ref,       # (1, INFO_LEN) int32 SMEM — control signals
    *,
    capacity: int,
    specs: tuple,          # ((plane_idx | -1, kind), ...) per accumulator
    checked: bool,
    grow_bound: bool,
    threshold: int,        # load-factor pause threshold (count > threshold)
    bound_slack: int,      # bound-headroom pause threshold (GROW only)
    collect_events: bool,
):
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _resume():
        # Adopt the carried state into the persistent output blocks; the
        # control vector starts clean (halts are per-launch, count carries).
        tkeys_ref[...] = tkeys_in_ref[...]
        ttks_ref[...] = ttks_in_ref[...]
        kbt_ref[...] = kbt_in_ref[...]
        accs_ref[...] = accs_in_ref[...]
        events_ref[...] = events_in_ref[...]
        info_ref[0, INFO_COUNT] = count_in_ref[0]
        info_ref[0, INFO_FIRST_HALT] = jnp.int32(NO_HALT)
        info_ref[0, INFO_SAT] = 0
        info_ref[0, INFO_HALTED] = 0

    count0 = info_ref[0, INFO_COUNT]
    halted0 = info_ref[0, INFO_HALTED]
    live = (i >= start_ref[0]) & (halted0 == 0)

    if checked:
        # Pre-morsel room check (§4.4 pause-before-overflow): a pausing
        # morsel commits NOTHING — the host migrates/grows and resumes here.
        needs_room = count0 > threshold
        if grow_bound:
            needs_room = needs_room | (count0 > bound_slack)
        pause = live & needs_room
        work = live & jnp.logical_not(needs_room)
        fh = info_ref[0, INFO_FIRST_HALT]
        info_ref[0, INFO_HALTED] = jnp.where(pause, 1, halted0)
        info_ref[0, INFO_FIRST_HALT] = jnp.where(pause, jnp.minimum(fh, i), fh)
        if collect_events:
            ev = events_ref[0, :]
            events_ref[0, :] = ev.at[obs_metrics.EVT_PAUSES].add(
                pause.astype(jnp.int32)
            )
    else:
        work = live

    @pl.when(work)
    def _morsel():
        keys = keys_ref[0, :]
        m = keys.shape[0]
        g = kbt_ref.shape[1]
        lane = jax.lax.iota(jnp.int32, m)
        valid = keys != EMPTY_I32
        slot0 = _slot_hash_i32(keys, capacity)
        # One wrap of linear probing plus one claim round per possible
        # winner — past this, remaining lanes provably face a saturated
        # table (same bound as ticket_hash / core.ticketing).
        max_rounds = 2 * capacity + 2

        # -- phase 1: ticket the morsel (claim protocol of ticket_hash) ----
        def cond(st):
            return jnp.any(st[4]) & (st[7] < max_rounds)

        def body(st):
            tkeys, ttks, kbt, slot, active, out, count, rounds, plen = st
            plen = plen + active.astype(jnp.int32)
            probed_key = jnp.take(tkeys, slot)
            probed_tk = jnp.take(ttks, slot)
            hit = active & (probed_tk != 0) & (probed_key == keys)
            out = jnp.where(hit, probed_tk, out)
            active = active & ~hit
            collide = active & (probed_tk != 0) & (probed_key != keys)
            slot = jnp.where(collide, (slot + 1) & (capacity - 1), slot)
            trying = active & (probed_tk == 0)
            claim_slot = jnp.where(trying, slot, capacity)
            claims = (
                jnp.full((capacity,), m, jnp.int32)
                .at[claim_slot].min(lane, mode="drop")
            )
            won = trying & (jnp.take(claims, slot) == lane)
            rank = jnp.cumsum(won.astype(jnp.int32)) - 1
            new_ticket = count + 1 + rank
            pub_slot = jnp.where(won, slot, capacity)
            tkeys = tkeys.at[pub_slot].set(keys, mode="drop")
            ttks = ttks.at[pub_slot].set(new_ticket, mode="drop")
            kbt_idx = jnp.where(won, new_ticket - 1, g)
            kbt = kbt.at[kbt_idx].set(keys, mode="drop")
            out = jnp.where(won, new_ticket, out)
            active = active & ~won
            count = count + jnp.sum(won.astype(jnp.int32))
            return tkeys, ttks, kbt, slot, active, out, count, rounds + 1, plen

        init = (
            tkeys_ref[0, :], ttks_ref[0, :], kbt_ref[0, :], slot0, valid,
            jnp.zeros((m,), jnp.int32), count0, jnp.zeros((), jnp.int32),
            jnp.zeros((m,), jnp.int32),
        )
        tkeys, ttks, kbt, _, active, tickets1, count, _, plen = (
            jax.lax.while_loop(cond, body, init)
        )

        # Inserts publish even from a saturated morsel — replay takes the
        # fast-path lookup, so they are idempotent (the scan pipeline's
        # commit rule); state updates below commit only when every valid
        # lane resolved.
        tkeys_ref[0, :] = tkeys
        ttks_ref[0, :] = ttks
        kbt_ref[0, :] = kbt
        info_ref[0, INFO_COUNT] = count

        sat = jnp.any(active)
        info_ref[0, INFO_SAT] = jnp.where(sat, 1, info_ref[0, INFO_SAT])
        if checked:
            commit = jnp.logical_not(sat)
            halted_now = info_ref[0, INFO_HALTED]
            fh2 = info_ref[0, INFO_FIRST_HALT]
            info_ref[0, INFO_HALTED] = jnp.where(sat, 1, halted_now)
            info_ref[0, INFO_FIRST_HALT] = jnp.where(
                sat, jnp.minimum(fh2, i), fh2
            )
        else:
            # Unchecked (perfect-estimate regime): unresolved lanes drop
            # individually, exactly like the split route's parked tickets.
            commit = jnp.bool_(True)

        # -- phase 2: consume the tickets in-register (never hit HBM) ------
        do = valid & (tickets1 > 0) & commit
        tt = jnp.where(do, tickets1 - 1, g)
        for s, (plane, kind) in enumerate(specs):
            if plane < 0:
                v = jnp.ones((m,), jnp.float32)
            else:
                v = vals_ref[plane, 0, :]
            vv = jnp.where(do, v, _NEUTRAL[kind])
            acc = accs_ref[s, 0, :]
            if kind in ("sum", "count"):
                accs_ref[s, 0, :] = acc.at[tt].add(vv, mode="drop")
            elif kind == "min":
                accs_ref[s, 0, :] = acc.at[tt].min(vv, mode="drop")
            else:
                accs_ref[s, 0, :] = acc.at[tt].max(vv, mode="drop")

        if collect_events:
            # Mirror engine.groupby.accumulate_scan_events: committed-morsel
            # semantics for row/probe counts, pause events fire regardless.
            c = commit.astype(jnp.int32)
            n_valid = jnp.sum(valid.astype(jnp.int32))
            ev = events_ref[0, :]
            ev = ev.at[obs_metrics.EVT_MORSELS].add(c)
            ev = ev.at[obs_metrics.EVT_ROWS].add(c * n_valid)
            ev = ev.at[obs_metrics.EVT_ROWS_MASKED].add(
                c * (jnp.int32(m) - n_valid)
            )
            ev = ev.at[obs_metrics.EVT_PROBE_STEPS].add(c * jnp.sum(plen))
            ev = ev.at[obs_metrics.EVT_PROBE_SATURATIONS].add(
                sat.astype(jnp.int32)
            )
            halt_now = sat if checked else jnp.bool_(False)
            ev = ev.at[obs_metrics.EVT_PAUSES].add(halt_now.astype(jnp.int32))
            # searchsorted(edges, plen, side="right") with the static edge
            # tuple unrolled (pallas kernels cannot capture array constants)
            bucket = jnp.zeros((m,), jnp.int32)
            for e in obs_metrics.PROBE_HIST_EDGES:
                bucket = bucket + (plen >= e).astype(jnp.int32)
            idx = jnp.where(
                valid & commit,
                jnp.int32(obs_metrics.NUM_EVENTS) + bucket,
                jnp.int32(obs_metrics.EVENT_VEC_LEN),
            )
            ev = ev.at[idx].add(1, mode="drop")
            events_ref[0, :] = ev


@functools.partial(
    jax.jit,
    static_argnames=(
        "specs", "checked", "grow_bound", "threshold", "bound_slack",
        "collect_events", "interpret",
    ),
)
def fused_consume(
    state: FusedState,
    keys: jnp.ndarray,     # (P * npm, M) int32, EMPTY_I32-padded
    values: jnp.ndarray,   # (V, P * npm, M) f32
    start: jnp.ndarray,    # (P,) int32 — resume morsel per program
    *,
    specs: tuple,
    checked: bool = True,
    grow_bound: bool = True,
    threshold: int = 0,
    bound_slack: int = 0,
    collect_events: bool = False,
    interpret: bool | None = None,
):
    """Run one launch of the fused kernel over a morselized chunk.

    The grid is ``(programs, morsels_per_program)``: program ``p`` owns
    morsels ``[p*npm, (p+1)*npm)`` and its own constant-index table block.
    Returns ``(new_state, info)`` where ``info`` is the (P, INFO_LEN) SMEM
    control vector — the host reads it ONCE per chunk (the same sync
    cadence as the scan pipeline's halt flags) to drive pause → grow →
    resume."""
    from repro.kernels.ops import _auto_interpret

    interpret = _auto_interpret(interpret)
    P, C = state.tkeys.shape
    S, _, G = state.accs.shape
    V, total, M = values.shape
    assert total == keys.shape[0] and total % P == 0
    npm = total // P
    ev_len = obs_metrics.EVENT_VEC_LEN

    kernel = functools.partial(
        _fused_kernel, capacity=C, specs=specs, checked=checked,
        grow_bound=grow_bound, threshold=threshold, bound_slack=bound_slack,
        collect_events=collect_events,
    )

    def smem(shape, imap):
        return pl.BlockSpec(
            memory_space=pltpu.SMEM, block_shape=shape, index_map=imap
        )

    out_shape = (
        jax.ShapeDtypeStruct((P, C), jnp.int32),
        jax.ShapeDtypeStruct((P, C), jnp.int32),
        jax.ShapeDtypeStruct((P, G), jnp.int32),
        jax.ShapeDtypeStruct((S, P, G), jnp.float32),
        jax.ShapeDtypeStruct((P, ev_len), jnp.int32),
        jax.ShapeDtypeStruct((P, INFO_LEN), jnp.int32),
    )
    tkeys, ttks, kbt, accs, events, info = pl.pallas_call(
        kernel,
        grid=(P, npm),
        in_specs=[
            smem((1,), lambda p, i: (p,)),                            # start
            smem((1,), lambda p, i: (p,)),                            # count
            pl.BlockSpec((1, M), lambda p, i: (p * npm + i, 0)),      # keys
            pl.BlockSpec((V, 1, M), lambda p, i: (0, p * npm + i, 0)),
            pl.BlockSpec((1, C), lambda p, i: (p, 0)),                # tkeys
            pl.BlockSpec((1, C), lambda p, i: (p, 0)),                # ttks
            pl.BlockSpec((1, G), lambda p, i: (p, 0)),                # kbt
            pl.BlockSpec((S, 1, G), lambda p, i: (0, p, 0)),          # accs
            pl.BlockSpec((1, ev_len), lambda p, i: (p, 0)),           # events
        ],
        out_specs=(
            pl.BlockSpec((1, C), lambda p, i: (p, 0)),
            pl.BlockSpec((1, C), lambda p, i: (p, 0)),
            pl.BlockSpec((1, G), lambda p, i: (p, 0)),
            pl.BlockSpec((S, 1, G), lambda p, i: (0, p, 0)),
            pl.BlockSpec((1, ev_len), lambda p, i: (p, 0)),
            smem((1, INFO_LEN), lambda p, i: (p, 0)),
        ),
        out_shape=out_shape,
        interpret=interpret,
    )(
        start, state.count, keys, values, state.tkeys, state.ttks,
        state.kbt, state.accs, state.events,
    )
    new_state = FusedState(
        tkeys=tkeys, ttks=ttks, kbt=kbt, accs=accs,
        count=info[:, INFO_COUNT], events=events,
    )
    return new_state, info


def fused_groupby_pallas(
    keys: jnp.ndarray,
    values: jnp.ndarray,
    *,
    capacity: int,
    max_groups: int,
    kind: str = "sum",
    morsel_size: int = 1024,
    interpret: bool | None = None,
):
    """One fused pass: keys+values morsels → (key_by_ticket, acc, count).

    Single-aggregate convenience wrapper over :func:`fused_consume` (fresh
    state, one program, unchecked) — the original prototype surface, kept
    for direct kernel callers and the parity tests.  Engine code selects
    the fused route via ``ExecutionPolicy.kernel="fused"`` instead."""
    assert capacity & (capacity - 1) == 0
    n = keys.shape[0]
    assert n % morsel_size == 0
    num = n // morsel_size
    k2 = keys.astype(jnp.uint32).astype(jnp.int32).reshape(num, morsel_size)
    v2 = values.astype(jnp.float32).reshape(1, num, morsel_size)
    state = init_fused_state(
        capacity=capacity, max_groups=max_groups, kinds=(kind,)
    )
    specs = ((-1 if kind == "count" else 0, kind),)
    state, _ = fused_consume(
        state, k2, v2, jnp.zeros((1,), jnp.int32), specs=specs,
        checked=False, grow_bound=False, collect_events=False,
        interpret=interpret,
    )
    acc = state.accs[0, 0]
    if kind in ("min", "max"):
        acc = jnp.where(jnp.isinf(acc), jnp.nan, acc)
    return state.kbt[0].astype(jnp.uint32), acc, state.count[0]
