"""Partitioned aggregation baseline (paper §2.2, Fig. 1 — Leis et al. [16]).

Two stages: (1) *local pre-aggregation* — each worker aggregates its morsels
into a small fixed-size hash table, spilling rows that miss; (2)
*partition-wise aggregation* — pre-aggregates and spills are exchanged by key
partition and each worker finishes its partitions alone.

TPU adaptation: "worker" = vmapped lane group on one core (this file) or a
mesh device (``core/distributed.py``, where the exchange is a real
``all_to_all``).  The pre-agg table is direct-mapped and morsel-vectorized —
claims resolve with the same scatter-min vote used in ticketing, and rows
that lose a claim or collide spill, exactly reproducing the paper's
"constant spilling at high cardinality ⇒ every tuple aggregated twice"
overhead that fully concurrent aggregation removes.

This is the comparison baseline for Fig. 6 / Table 2 benchmarks; it is
deliberately implemented with the same care as the concurrent path (the
paper's claim is about algorithms, not about a strawman).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import ticketing as tk
from repro.core import updates as up
from repro.core.aggregation import GroupByResult
from repro.core.hashing import EMPTY_KEY, slot_hash


class PreAggState(NamedTuple):
    keys: jnp.ndarray  # (C,) uint32
    vals: jnp.ndarray  # (C,) f32 partial aggregates
    cnts: jnp.ndarray  # (C,) f32 partial counts (for mean / count kinds)


def make_preagg(capacity: int, kind: str) -> PreAggState:
    return PreAggState(
        keys=jnp.full((capacity,), EMPTY_KEY, jnp.uint32),
        vals=jnp.full((capacity,), up.neutral(kind), jnp.float32),
        cnts=jnp.zeros((capacity,), jnp.float32),
    )


def preagg_morsel(state: PreAggState, keys, values, kind: str):
    """Vectorized local pre-aggregation of one morsel into the fixed table.

    Returns (state, spill_mask): rows with spill_mask=True missed the table
    (slot taken by another key, or lost an install race) and must be spilled
    downstream as raw rows.
    """
    c = state.keys.shape[0]
    lane = jnp.arange(keys.shape[0], dtype=jnp.int32)
    valid = keys != EMPTY_KEY
    slot = slot_hash(keys, c)

    def try_round(state, pending):
        tkey = jnp.take(state.keys, slot)
        hit = pending & (tkey == keys)
        empty = pending & (tkey == EMPTY_KEY)
        # install race: scatter-min vote on empty slots
        claim_slot = jnp.where(empty, slot, c)
        claims = jnp.full((c + 1,), lane.shape[0], jnp.int32).at[claim_slot].min(lane)
        won = empty & (jnp.take(claims, slot) == lane)
        new_keys = jnp.concatenate([state.keys, jnp.full((1,), EMPTY_KEY, jnp.uint32)])
        new_keys = new_keys.at[jnp.where(won, slot, c)].set(keys)[:c]
        # aggregate hits and winners in place
        upd = hit | won
        uslot = jnp.where(upd, slot, c)
        vals = jnp.concatenate([state.vals, jnp.zeros((1,), jnp.float32)])
        cnts = jnp.concatenate([state.cnts, jnp.zeros((1,), jnp.float32)])
        v = jnp.where(upd, values, up.neutral(kind))
        if kind in ("sum", "count"):
            vals = vals.at[uslot].add(jnp.where(upd, values if kind == "sum" else 1.0, 0.0))
        elif kind == "min":
            vals = vals.at[uslot].min(v)
        elif kind == "max":
            vals = vals.at[uslot].max(v)
        cnts = cnts.at[uslot].add(jnp.where(upd, 1.0, 0.0))
        return PreAggState(new_keys, vals[:c], cnts[:c]), pending & ~upd

    # Round 1: hits + installs. Round 2: rows that lost an install race to
    # the SAME key now hit the fast path (mirrors the ticketing retry). Rows
    # still pending after round 2 collide with a different key → spill.
    state, pending = try_round(state, valid)
    state, pending = try_round(state, pending)
    return state, pending


def partitioned_groupby(
    keys: jnp.ndarray,
    values: jnp.ndarray | None = None,
    *,
    kind: str = "count",
    max_groups: int,
    num_workers: int = 8,
    preagg_capacity: int = 1024,
    morsel_size: int | None = None,
    saturation: str = "unchecked",
) -> GroupByResult:
    """Single-device simulation of Leis-style partitioned aggregation with
    ``num_workers`` parallel workers (vmap).  Adapter over ``GroupByPlan``
    with ``strategy="partitioned"`` — the assembled pipeline runs behind
    the executor seam (``repro.engine.executors._PartitionedExecutor``,
    which invokes :func:`_partitioned_impl` below); pass
    ``saturation="raise"|"grow"`` for checked/recovering bounds.  The
    distributed version with a real all_to_all lives in
    core/distributed.py."""
    from repro.engine.plan_api import (
        AggSpec,
        ExecutionPolicy,
        GroupByPlan,
        arrays_as_table,
        as_group_result,
        execute,
    )

    table, _ = arrays_as_table(keys, values)
    agg = AggSpec("count") if kind == "count" else AggSpec(kind, "v")
    plan = GroupByPlan(
        keys=("__key__",), aggs=(agg,), strategy="partitioned",
        max_groups=max_groups, saturation=saturation, raw_keys=True,
        execution=ExecutionPolicy(
            num_workers=num_workers, preagg_capacity=preagg_capacity,
            preagg_morsel=morsel_size,
        ),
    )
    return as_group_result(execute(plan, table), agg)


@functools.partial(
    jax.jit,
    static_argnames=("kind", "max_groups", "num_workers", "preagg_capacity", "morsel_size"),
)
def _partitioned_impl(
    keys: jnp.ndarray,
    values: jnp.ndarray | None = None,
    *,
    kind: str = "count",
    max_groups: int,
    num_workers: int = 8,
    preagg_capacity: int = 1024,
    morsel_size: int | None = None,
) -> GroupByResult:
    """The jitted preagg → exchange → partition-wise pipeline (executor
    backend; reach it through ``GroupByPlan(strategy="partitioned")``)."""
    keys = keys.reshape(-1).astype(jnp.uint32)
    n = keys.shape[0]
    if values is None:
        values = jnp.ones((n,), jnp.float32)
    values = values.reshape(-1).astype(jnp.float32)
    assert n % num_workers == 0, "pad input to a multiple of num_workers"
    kw = keys.reshape(num_workers, -1)
    vw = values.reshape(num_workers, -1)
    chunk = kw.shape[1]
    msize = morsel_size or chunk
    assert chunk % msize == 0

    def worker(kc, vc):
        st = make_preagg(preagg_capacity, kind)

        def step(st, m):
            mk, mv = m
            st, spill = preagg_morsel(st, mk, mv, kind)
            return st, spill

        st, spills = jax.lax.scan(
            step, st, (kc.reshape(-1, msize), vc.reshape(-1, msize))
        )
        return st, spills.reshape(-1)

    states, spill_masks = jax.vmap(worker)(kw, vw)

    # ---- exchange: flatten pre-agg entries + raw spilled rows -------------
    # Pre-agg entries carry (key, partial_val, partial_cnt); spills carry the
    # raw row (key, value, 1).  In the single-device simulation the
    # "exchange" is a concatenation; the partition-parallel final phase is
    # order-insensitive so this is behaviourally identical.
    ekeys = states.keys.reshape(-1)
    evals = states.vals.reshape(-1)
    ecnts = states.cnts.reshape(-1)

    skeys = jnp.where(spill_masks.reshape(-1), kw.reshape(-1), EMPTY_KEY)
    svals_raw = vw.reshape(-1)
    if kind == "count":
        svals = jnp.where(spill_masks.reshape(-1), 1.0, 0.0)
    elif kind == "sum":
        svals = jnp.where(spill_masks.reshape(-1), svals_raw, 0.0)
    else:
        svals = jnp.where(spill_masks.reshape(-1), svals_raw, up.neutral(kind))
    scnts = jnp.where(spill_masks.reshape(-1), 1.0, 0.0)

    allk = jnp.concatenate([ekeys, skeys])
    allv = jnp.concatenate([evals, svals])
    allc = jnp.concatenate([ecnts, scnts])

    # ---- partition-wise final aggregation (sort = radix partition) -------
    tickets, key_by_ticket, count = tk.sort_ticketing(allk)
    acc = up.init_acc(max_groups, kind)
    acc = up.sort_segment_update(acc, tickets, allv, kind="min" if kind == "min" else "max" if kind == "max" else "sum")
    # ticket slots number the input's entries (pre-aggregated plus
    # spilled); a bound above that (a stream chunk under a large bound)
    # would leave fewer keys than accumulators: pad, so both have
    # ``max_groups`` rows
    kbt = key_by_ticket[:max_groups]
    kbt = jnp.pad(kbt, (0, max_groups - kbt.shape[0]), constant_values=EMPTY_KEY)
    return GroupByResult(kbt, up.finalize(kind, acc), count)
