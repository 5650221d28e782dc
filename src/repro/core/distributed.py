"""Distributed group aggregation over a device mesh (paper "threads" ⇒ devices).

Two strategies, mirroring the paper's central comparison at mesh scale:

* :func:`concurrent_groupby_sharded` — the **fully concurrent / thread-local**
  analogue.  Every device runs the single-core concurrent pipeline (ticket →
  dense update) over its shard of the rows, producing a dense ticket-indexed
  partial-aggregate vector *keyed identically across devices* (the global
  key→ticket map is made consistent by ticketing against a shared key-space
  hash: slot position IS the ticket — a "global hash table" whose slots are
  replicated and whose merge is additive).  The end merge is ONE
  ``psum``/``reduce_scatter`` over a dense vector — the paper's "trivially
  parallel, cache-efficient" merge (§3.2) becomes a single all-reduce, the
  literal transpose of partitioning's all_to_all.

* :func:`partitioned_groupby_sharded` — the Leis baseline: local pre-agg,
  radix partition by key hash, ``all_to_all`` exchange, final local agg.

Consistency note (honest adaptation): CPU threads share one mutating table —
tickets are assigned first-come by CAS.  Devices cannot share memory, so the
concurrent strategy establishes the global key→ticket map with a **union
build**: each device tickets its rows locally, all-gathers the per-device
*unique key lists* (tiny: bounded by cardinality, not rows — this is the
crucial asymmetry the paper's indirection buys us), and then every device
deterministically replays the concatenated key lists into its own copy of
the "global" table.  Determinism of the replay order (device-rank order) is
the TPU analogue of CAS winner arbitration: every device computes the *same*
table, so ticket-indexed dense vectors are commonly indexed across the mesh
and the merge is one ``psum`` — the paper's "all vectors are in the same
(ticket) order ⇒ merge is trivially parallel and cache efficient" (§3.2),
made literal.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import resize
from repro.core import ticketing as tk
from repro.core import updates as up
from repro.core.aggregation import GroupByResult
from repro.core.hashing import (EMPTY_KEY, partition_hash, slot_hash,
                                table_capacity)
from repro.core.partitioned import make_preagg, preagg_morsel


# ---------------------------------------------------------------------------
# Streaming sharded consume: per-device state carried ACROSS chunks.
#
# The buffered PR-2 path re-ran the whole mesh pipeline over every row at
# finalize — O(total rows) host memory on a stream.  The streaming contract
# below is the paper's thread-local method made incremental: each device
# owns a local ticket table + dense partial-aggregate vector (the "carry"),
# every chunk is shard_map'ed over the mesh and folded into that carry, and
# the cross-device merge (dense psum union or all_to_all exchange) runs ONCE
# at finalize over state that is O(devices × capacity), independent of how
# many chunks streamed through.


_MERGE_KIND = {"count": "sum", "sum": "sum", "min": "min", "max": "max"}


class ShardedCarry(NamedTuple):
    """Per-device streaming aggregation state (leading axis = mesh devices).

    ``keys/tickets`` are each device's probe table, ``kbt`` its ticket-
    ordered unique-key list (the only thing the merge ever communicates —
    the paper's indirection payoff), ``acc`` its dense ticket-indexed
    partial aggregates: a full ``updates.AggState`` pytree whose leaves are
    ``(ndev, max_local)`` — one accumulator per ``(column, kind)`` spec, so
    sharded plans carry multi-aggregate/mean queries exactly like the
    single-device engine.  ``ovf`` is sticky per device: local tickets past
    the local bound, or rows dropped by a saturated probe table.
    """

    keys: jnp.ndarray     # (ndev, capacity) uint32
    tickets: jnp.ndarray  # (ndev, capacity) int32
    kbt: jnp.ndarray      # (ndev, max_local) uint32
    count: jnp.ndarray    # (ndev,) int32
    ovf: jnp.ndarray      # (ndev,) bool
    acc: up.AggState      # leaves (ndev, max_local) float32

    @property
    def capacity(self) -> int:
        return self.keys.shape[1]

    @property
    def max_local(self) -> int:
        return self.kbt.shape[1]


def make_sharded_carry(ndev: int, max_local: int, specs,
                       capacity: int | None = None) -> ShardedCarry:
    """``specs`` = [(column|None, kind), ...] as produced by
    ``engine.groupby.expand_agg_specs`` (mean already split into
    sum+count)."""
    specs = tuple(specs)
    cap = capacity or table_capacity(max_local)
    return ShardedCarry(
        keys=jnp.full((ndev, cap), EMPTY_KEY, jnp.uint32),
        tickets=jnp.zeros((ndev, cap), jnp.int32),
        kbt=jnp.full((ndev, max_local), EMPTY_KEY, jnp.uint32),
        count=jnp.zeros((ndev,), jnp.int32),
        ovf=jnp.zeros((ndev,), jnp.bool_),
        acc=up.AggState(specs, tuple(
            up.init_acc(max_local, k)[None].repeat(ndev, axis=0)
            for _, k in specs
        )),
    )


def make_sharded_consume_step(mesh, axis: str, *, update: str,
                              load_factor: float, checked: bool,
                              collect_events: bool = False):
    """Build the jitted per-chunk consume step: shard_map over the mesh,
    each device folding its (num_morsels, morsel_rows) slice of the chunk
    into its carried table + accumulator with an inner ``lax.scan`` — the
    single-core scan-compiled pipeline replicated per device.

    ``checked=True`` runs the engine's in-scan pause protocol (§4.4 at mesh
    scale) — the SAME morsel body as the single-device consume scan
    (``engine.groupby.make_pause_scan_body``), so the pause-commits-nothing
    invariant lives in one place: before each morsel a device pauses when
    its load factor or its bound headroom is crossed, and the returned
    per-device halt flags let the host migrate/widen every device's table
    and resume each device at ITS OWN paused morsel (``start`` is a
    per-device vector — devices that finished replay nothing).

    ``checked=False`` is the zero-sync regime: no pauses, rows past a
    saturated table or the local bound drop with only the sticky per-device
    ``ovf`` flag recording the loss (read once at finalize by the
    raise policy, never by unchecked).

    ``collect_events=True`` threads a per-device ``(ndev, EVENT_VEC_LEN)``
    int32 event vector (obs.metrics layout) as an extra step input/output —
    ``step(carry, km, vm, start, events)`` → ``(carry, halts, events)`` —
    accumulated device-side by the SAME shared pause body, read back only at
    finalize (zero extra syncs).  Default off: the step signature and the
    traced program are unchanged.
    """
    update_fn = up.get_update_fn(update)

    def local(keys, tickets, kbt, count, ovf, acc, km, vm, start, *maybe_ev):
        from repro.engine.groupby import accumulate_scan_events, make_pause_scan_body

        table = tk.TicketTable(
            keys[0], tickets[0], kbt[0], count[0], ovf[0]
        )
        lacc = jax.tree_util.tree_map(lambda x: x[0], acc)
        km0 = km[0]
        vm0 = {c: v[0] for c, v in vm.items()}
        st = start[0]
        ev0 = maybe_ev[0][0] if collect_events else None
        capacity = table.capacity
        threshold = int(load_factor * capacity)
        bound_slack = table.max_groups - km0.shape[1]
        idxs = jnp.arange(km0.shape[0], dtype=jnp.int32)

        if not checked:
            def body(carry, xs):
                if collect_events:
                    table, lacc, ev = carry
                else:
                    table, lacc = carry
                k, v = xs
                if collect_events:
                    tks, table, probe_len = tk.get_or_insert(
                        table, k, count_probes=True
                    )
                else:
                    tks, table = tk.get_or_insert(table, k)
                dropped = jnp.any((tks < 0) & (k != jnp.uint32(EMPTY_KEY)))
                table = table._replace(overflowed=table.overflowed | dropped)
                lacc = up.update_agg_state(lacc, tks, v, update_fn)
                if collect_events:
                    ev = accumulate_scan_events(
                        ev, k, probe_len, jnp.ones((), jnp.bool_), dropped,
                        jnp.zeros((), jnp.bool_),
                    )
                    return (table, lacc, ev), jnp.zeros((), jnp.bool_)
                return (table, lacc), jnp.zeros((), jnp.bool_)

            if collect_events:
                (table, lacc, ev0), halts = jax.lax.scan(
                    body, (table, lacc, ev0), (km0, vm0)
                )
            else:
                (table, lacc), halts = jax.lax.scan(body, (table, lacc), (km0, vm0))
        else:
            body = make_pause_scan_body(
                st, threshold, bound_slack,
                lambda lacc, tks, v: up.update_agg_state(lacc, tks, v, update_fn),
                count_events=collect_events,
            )
            if collect_events:
                (table, lacc, _, ev0), halts = jax.lax.scan(
                    body, (table, lacc, jnp.zeros((), jnp.bool_), ev0),
                    (idxs, km0, vm0),
                )
            else:
                (table, lacc, _), halts = jax.lax.scan(
                    body, (table, lacc, jnp.zeros((), jnp.bool_)), (idxs, km0, vm0)
                )
        out = (
            table.keys[None], table.tickets[None], table.key_by_ticket[None],
            table.count[None], table.overflowed[None],
            jax.tree_util.tree_map(lambda x: x[None], lacc), halts[None],
        )
        if collect_events:
            out = out + (ev0[None],)
        return out

    in_specs = (
        P(axis, None), P(axis, None), P(axis, None), P(axis), P(axis),
        P(axis, None), P(axis, None, None), P(axis, None, None), P(axis),
    )
    out_specs = (
        P(axis, None), P(axis, None), P(axis, None), P(axis), P(axis),
        P(axis, None), P(axis, None),
    )
    if collect_events:
        in_specs = in_specs + (P(axis, None),)
        out_specs = out_specs + (P(axis, None),)
    fn = shard_map(
        local, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )
    jitted = jax.jit(fn)

    def step(carry: ShardedCarry, km, vm, start, events=None):
        args = (
            carry.keys, carry.tickets, carry.kbt, carry.count, carry.ovf,
            carry.acc, km, vm, start,
        )
        if collect_events:
            keys, tickets, kbt, count, ovf, acc, halts, events = jitted(
                *args, events
            )
            return ShardedCarry(keys, tickets, kbt, count, ovf, acc), halts, events
        keys, tickets, kbt, count, ovf, acc, halts = jitted(*args)
        return ShardedCarry(keys, tickets, kbt, count, ovf, acc), halts

    return step


def replicated(tree):
    """Re-place every mesh-sharded leaf of ``tree`` replicated on its own
    mesh.  Gathers over an array whose type carries a sharded axis (an
    ``Explicit``-axis mesh, the ``jax.make_mesh`` default) need an
    ``out_sharding``; host-side bookkeeping over O(devices × max_local)
    carried state runs on replicated copies instead."""

    def one(x):
        sh = getattr(x, "sharding", None)
        if isinstance(sh, NamedSharding) and sh.spec != P():
            return jax.device_put(x, sh.update(spec=P()))
        return x

    return jax.tree_util.tree_map(one, tree)


def grow_sharded_carry(carry: ShardedCarry, new_max_local: int,
                       new_capacity: int) -> ShardedCarry:
    """Mesh analogue of the operator's pause-time growth: widen every
    device's bound (pad ``kbt`` + every accumulator with its kind's neutral
    — tickets are stable) and/or migrate every device's probe table (vmapped
    contention-less §4.4 migration, over a replicated copy of the carry;
    the next consume step re-shards it).  Uniform across devices so shapes
    stay static."""
    carry = replicated(carry)
    kbt, acc = carry.kbt, carry.acc
    if new_max_local > carry.max_local:
        ndev, pad = kbt.shape[0], new_max_local - carry.max_local
        kbt = jnp.concatenate(
            [kbt, jnp.full((ndev, pad), EMPTY_KEY, jnp.uint32)], axis=1
        )
        acc = up.AggState(acc.specs, tuple(
            jnp.concatenate(
                [a, jnp.full((ndev, pad), up.neutral(k, a.dtype), a.dtype)],
                axis=1,
            )
            for (_, k), a in zip(acc.specs, acc.accs)
        ))
    keys, tickets = carry.keys, carry.tickets
    if new_capacity > carry.capacity:
        migrated = jax.vmap(
            lambda k, t, kb, c, o: resize.migrate(
                tk.TicketTable(k, t, kb, c, o), new_capacity
            )
        )(keys, tickets, kbt, carry.count, carry.ovf)
        keys, tickets, kbt = migrated.keys, migrated.tickets, migrated.key_by_ticket
    return ShardedCarry(keys, tickets, kbt, carry.count, carry.ovf, acc)


def rebucket_sharded_carry(carry: ShardedCarry, new_ndev: int, *,
                           load_factor: float = 0.5,
                           max_local: int | None = None):
    """Re-bucket a streamed :class:`ShardedCarry` onto a mesh with a
    DIFFERENT device count — the elastic re-mesh primitive (device-loss
    recovery and restore-on-a-new-mesh both lower to this).

    Migration to a different mesh is the same table re-bucketing problem as
    growing, just across devices instead of capacities: each carried
    ``(key, partial)`` entry is reassigned by the SAME hash-partition rule
    the ``all_to_all`` exchange merge uses (``partition_hash(key, ndev, seed=7)``),
    entries of one key that were ticketed on several source devices fold
    with their spec's merge kind (sum/min/max — exactly what the finalize
    merge would have done), and each destination device union-replays its
    assigned keys into a fresh ticket table (the §4.4 migration, across the
    mesh).  Runs host-side over O(devices × max_local) carried state — rows
    never move, the paper's indirection payoff again.

    The per-device ``ovf`` loss flags are sticky GLOBAL semantics (keys
    already dropped stay dropped), so every survivor inherits their OR.
    Returns ``(carry, max_local)`` sized for ``new_ndev`` devices; pass
    ``max_local`` to keep a caller-contracted local bound (it is raised
    automatically if the folded entries need more room).
    """
    assert new_ndev >= 1, new_ndev
    kbt, counts, ovf = jax.device_get((carry.kbt, carry.count, carry.ovf))
    kbt = np.asarray(kbt)
    counts = np.asarray(counts)
    specs = carry.acc.specs
    accs = [np.asarray(a) for a in jax.device_get(carry.acc.accs)]
    # flatten every device's valid ticket prefix into one entry list
    sel = [
        (d, int(c)) for d, c in enumerate(counts.tolist()) if int(c) > 0
    ]
    if sel:
        all_keys = np.concatenate([kbt[d, :c] for d, c in sel])
        all_vals = [np.concatenate([a[d, :c] for d, c in sel]) for a in accs]
    else:
        all_keys = np.zeros((0,), np.uint32)
        all_vals = [np.zeros((0,), a.dtype) for a in accs]
    # destination device by the exchange merge's partition rule
    pid = np.asarray(jax.device_get(
        partition_hash(jnp.asarray(all_keys), new_ndev, seed=7)
    )).astype(np.int64) if all_keys.size else np.zeros((0,), np.int64)

    per_dev_keys, per_dev_vals = [], []
    for d in range(new_ndev):
        mine = pid == d
        keys_d = all_keys[mine]
        uniq, inv = np.unique(keys_d, return_inverse=True)
        folded = []
        for (_, kind), v in zip(specs, all_vals):
            mk = _MERGE_KIND[kind]
            if mk == "sum":
                acc = np.zeros(uniq.shape, v.dtype)
                np.add.at(acc, inv, v[mine])
            elif mk == "min":
                acc = np.full(uniq.shape, np.asarray(up.neutral("min")), v.dtype)
                np.minimum.at(acc, inv, v[mine])
            else:
                acc = np.full(uniq.shape, np.asarray(up.neutral("max")), v.dtype)
                np.maximum.at(acc, inv, v[mine])
            folded.append(acc)
        per_dev_keys.append(uniq)
        per_dev_vals.append(folded)

    need = max((k.shape[0] for k in per_dev_keys), default=0)
    new_max_local = max(need, max_local or 0, 64)
    cap = table_capacity(new_max_local, load_factor)
    any_ovf = bool(np.asarray(ovf).any())

    out_keys, out_tickets, out_kbt, out_count, out_acc = [], [], [], [], []
    for d in range(new_ndev):
        uniq = per_dev_keys[d]
        padded = jnp.concatenate([
            jnp.asarray(uniq, jnp.uint32),
            jnp.full((new_max_local - uniq.shape[0],), EMPTY_KEY, jnp.uint32),
        ])
        tickets, table = tk.get_or_insert(
            tk.make_table(cap, max_groups=new_max_local), padded
        )
        dev_accs = []
        for (_, kind), v in zip(specs, per_dev_vals[d]):
            acc = up.init_acc(new_max_local, kind)
            vpad = jnp.concatenate([
                jnp.asarray(v),
                jnp.full((new_max_local - v.shape[0],), up.neutral(kind),
                         acc.dtype),
            ])
            dev_accs.append(up.scatter_update(
                acc, tickets, vpad, kind=_MERGE_KIND[kind]
            ))
        out_keys.append(table.keys)
        out_tickets.append(table.tickets)
        out_kbt.append(table.key_by_ticket)
        out_count.append(table.count)
        out_acc.append(dev_accs)

    new_carry = ShardedCarry(
        keys=jnp.stack(out_keys),
        tickets=jnp.stack(out_tickets),
        kbt=jnp.stack(out_kbt),
        count=jnp.stack(out_count).reshape(-1).astype(jnp.int32),
        ovf=jnp.full((new_ndev,), any_ovf, jnp.bool_),
        acc=up.AggState(specs, tuple(
            jnp.stack([out_acc[d][j] for d in range(new_ndev)])
            for j in range(len(specs))
        )),
    )
    return new_carry, new_max_local


def sharded_psum_merge(mesh, axis: str, carry: ShardedCarry, *,
                       max_groups: int):
    """Dense-psum union merge of a streamed :class:`ShardedCarry` — steps
    2–5 of the fully concurrent mesh protocol (all-gather unique keys,
    deterministic union replay, ticket translation, one dense psum per
    accumulator), run over O(devices × max_local) carried state instead of
    over rows.

    Pure function of the carry, so mid-stream snapshots are free: the
    caller can merge, read, and keep consuming into the same carry.
    Returns ``(key_by_ticket, AggState, count, local_ovf, union_ovf)`` —
    RAW (unfinalized) merged accumulators in global ticket order (the
    result builder finalizes, composing mean from sum/count), plus the
    sticky per-device loss flags (psum'd) and the union-table overflow, for
    the saturation policy to inspect.
    """
    gstate, key_by_ticket, count, lovf, govf = _psum_merge(
        carry.kbt, carry.acc, carry.ovf, mesh=mesh, axis=axis,
        max_groups=max_groups,
    )
    return key_by_ticket, gstate, count, lovf, govf


@functools.partial(jax.jit, static_argnames=("mesh", "axis", "max_groups"))
def _psum_merge(kbt, acc, ovf, *, mesh, axis: str, max_groups: int):
    # one compiled program per (mesh, bound): op-by-op dispatch of the
    # shard_map body would compile every primitive on its own
    max_local = kbt.shape[1]
    # The union's probe table holds every gathered key, not just the bound:
    # a bound below the union then still counts the union exactly (tickets
    # past the bound only set the overflow flag), so GROW re-merges once at
    # the true size.  Sized to the bound alone, a full table keeps every
    # unplaced lane probing for 2·capacity rounds per merge.
    cap_global = table_capacity(max(max_groups, mesh.shape[axis] * max_local))

    def local(kbt, lacc, ovf):
        local_keys = kbt[0]
        all_keys = jax.lax.all_gather(local_keys, axis, tiled=True)
        gtickets, gtable = tk.get_or_insert(
            tk.make_table(cap_global, max_groups=max_groups), all_keys
        )
        rank = jax.lax.axis_index(axis)
        mine = jax.lax.dynamic_slice_in_dim(
            gtickets, rank * max_local, max_local
        )
        merged = []
        for (_, kind), la in zip(lacc.specs, tuple(
            jax.tree_util.tree_map(lambda x: x[0], lacc).accs
        )):
            merge_kind = _MERGE_KIND[kind]
            gacc = up.init_acc(max_groups, kind)
            gacc = up.scatter_update(gacc, mine, la, kind=merge_kind)
            if merge_kind == "sum":
                gacc = jax.lax.psum(gacc, axis)
            elif merge_kind == "min":
                gacc = -jax.lax.pmax(-gacc, axis)
            else:
                gacc = jax.lax.pmax(gacc, axis)
            merged.append(gacc)
        gstate = up.AggState(lacc.specs, tuple(merged))
        lovf = jax.lax.psum(ovf[0].astype(jnp.int32), axis)
        govf = gtable.overflowed.astype(jnp.int32)
        return gstate, gtable.key_by_ticket, gtable.count, lovf, govf

    return shard_map(
        local, mesh=mesh,
        in_specs=(P(axis, None), P(axis, None), P(axis)),
        out_specs=(P(), P(), P(), P(), P()),
        check_vma=False,
    )(kbt, acc, ovf)


def sharded_exchange_merge(mesh, axis: str, carry: ShardedCarry, *,
                           max_groups: int, partition_capacity: int | None = None):
    """All_to_all exchange merge of a streamed :class:`ShardedCarry` — the
    Leis baseline's exchange run over per-device LOCAL AGGREGATES (each
    device's carried ticket table is its pre-aggregation, complete and
    spill-free, bounded by max_local) instead of over buffered raw rows.
    Every accumulator of the carry's ``AggState`` rides the same exchange:
    bucket rows are ``(key, acc_0..acc_V)`` so one all_to_all pair moves a
    multi-aggregate query.

    Returns the partitioned strategy's native per-device layout
    ``(keys_p, vals_p, counts_p, overflow_p)`` — ``vals_p`` a tuple of RAW
    per-spec vectors aligned with ``carry.acc.specs`` — plus the psum'd
    sticky local loss flag.  ``overflow_p`` counts partition-bucket drops
    (static-shape exchange); callers grow ``partition_capacity`` and re-run
    — cheap, since the input is carried state, not rows.
    """
    cap = partition_capacity or max(2 * carry.max_local // mesh.shape[axis], 16)
    return _exchange_merge(
        carry.kbt, carry.acc, carry.ovf, mesh=mesh, axis=axis,
        max_groups=max_groups, cap=cap,
    )


@functools.partial(jax.jit, static_argnames=("mesh", "axis", "max_groups", "cap"))
def _exchange_merge(kbt, acc, ovf, *, mesh, axis: str, max_groups: int, cap: int):
    ndev = mesh.shape[axis]
    specs = acc.specs
    merge_kinds = tuple(_MERGE_KIND[k] for _, k in specs)

    def local(kbt, lacc, ovf):
        allk = kbt[0]
        allv = jnp.stack(
            tuple(jax.tree_util.tree_map(lambda x: x[0], lacc).accs), axis=1
        )  # (max_local, V)
        pid = partition_hash(allk, ndev, seed=7)
        pid = jnp.where(allk == EMPTY_KEY, ndev, pid)
        order = jnp.argsort(pid, stable=True)
        pk, pp = jnp.take(allk, order), jnp.take(pid, order)
        pv = jnp.take(allv, order, axis=0)
        pos = jnp.arange(pk.shape[0]) - jnp.searchsorted(pp, pp, side="left")
        overflow = jnp.sum((pos >= cap) & (pp < ndev))
        dest = jnp.where((pos < cap) & (pp < ndev), pp * cap + pos, ndev * cap)
        bk = jnp.full((ndev * cap + 1,), EMPTY_KEY, jnp.uint32).at[dest].set(pk)[:-1]
        neutral_row = jnp.stack([up.neutral(mk) for mk in merge_kinds])
        bv = jnp.broadcast_to(
            neutral_row, (ndev * cap + 1, len(specs))
        ).at[dest].set(pv)[:-1]
        bk = bk.reshape(ndev, cap)
        bv = bv.reshape(ndev, cap, len(specs))
        xk = jax.lax.all_to_all(bk, axis, split_axis=0, concat_axis=0, tiled=False)
        xv = jax.lax.all_to_all(bv, axis, split_axis=0, concat_axis=0, tiled=False)
        xk = xk.reshape(-1)
        xv = xv.reshape(-1, len(specs))
        tickets, key_by_ticket, cnt = tk.sort_ticketing(xk)
        vals = []
        for j, ((_, kind), mk) in enumerate(zip(specs, merge_kinds)):
            acc = up.init_acc(max_groups, kind)
            vals.append(up.sort_segment_update(acc, tickets, xv[:, j], kind=mk))
        lovf = jax.lax.psum(ovf[0].astype(jnp.int32), axis)
        return (
            key_by_ticket[:max_groups],
            tuple(vals),
            cnt.reshape(1),
            overflow.reshape(1).astype(jnp.int32),
            lovf,
        )

    return shard_map(
        local, mesh=mesh,
        in_specs=(P(axis, None), P(axis, None), P(axis)),
        out_specs=(P(axis), P(axis), P(axis), P(axis), P()),
        check_vma=False,
    )(kbt, acc, ovf)


def concurrent_groupby_sharded(
    mesh,
    keys,
    values=None,
    *,
    kind: str = "count",
    max_groups: int,
    axis: str = "data",
    max_local_groups: int | None = None,
    update: str = "scatter",
    saturation: str = "unchecked",
):
    """Fully concurrent aggregation across the mesh ``axis`` — adapter over
    ``GroupByPlan(strategy="sharded", shard_merge="dense_psum")``; the mesh
    protocol itself is :func:`_concurrent_sharded_impl` behind the executor
    seam.  Pass ``saturation="raise"|"grow"`` for checked/recovering
    bounds (the default preserves the legacy unchecked contract)."""
    from repro.engine.executors import make_executor
    from repro.engine.plan_api import (
        AggSpec,
        ExecutionPolicy,
        GroupByPlan,
        arrays_as_table,
        as_group_result,
    )

    table, _ = arrays_as_table(keys, values)
    agg = AggSpec("count") if kind == "count" else AggSpec(kind, "v")
    plan = GroupByPlan(
        keys=("__key__",), aggs=(agg,), strategy="sharded",
        max_groups=max_groups, saturation=saturation, raw_keys=True,
        execution=ExecutionPolicy(
            mesh=mesh, axis=axis, shard_merge="dense_psum",
            max_local_groups=max_local_groups, update=update,
        ),
    )
    ex = make_executor(plan)
    ex.open()
    ex.consume(table)
    return as_group_result(ex.finalize(), agg)


def _concurrent_sharded_impl(
    mesh,
    keys,
    values=None,
    *,
    kind: str = "count",
    max_groups: int,
    axis: str = "data",
    max_local_groups: int | None = None,
    update: str = "scatter",
):
    """Mesh protocol for the fully concurrent strategy (executor backend).

    keys/values are sharded over ``axis`` on dim 0.  Protocol (thread-local
    method of §3.2 at mesh scale):

      1. local ticketing + dense update over the shard's rows;
      2. all-gather per-device unique key lists (≤ max_local_groups keys —
         cardinality-bounded, NOT row-bounded);
      3. deterministic union replay → identical global table everywhere;
      4. translate local tickets to global tickets (one gather);
      5. dense ``psum`` of ticket-indexed partial vectors == the merge.
    """
    if max_local_groups is None:
        max_local_groups = max_groups
    cap_local = table_capacity(max_local_groups)
    cap_global = table_capacity(max_groups)

    update_fn = up.get_update_fn(update)

    def local(kk, vv):
        kk = kk.reshape(-1)
        vv = vv.reshape(-1)
        # (1) local ticketing + local dense partial aggregates
        ltickets, ltable = tk.get_or_insert(
            tk.make_table(cap_local, max_groups=max_local_groups), kk
        )
        lacc = up.init_acc(max_local_groups, kind)
        lacc = update_fn(lacc, ltickets, vv, kind=kind)
        # (2) exchange unique keys only (the paper's indirection payoff:
        #     the communicated state is O(cardinality), rows never move)
        local_keys = ltable.key_by_ticket  # (max_local_groups,) ticket order
        all_keys = jax.lax.all_gather(local_keys, axis, tiled=True)
        # (3) deterministic union replay — same table on every device
        gtickets_of_all, gtable = tk.get_or_insert(
            tk.make_table(cap_global, max_groups=max_groups), all_keys
        )
        # (4) my keys sit at rank*max_local_groups in the gathered list
        rank = jax.lax.axis_index(axis)
        mine = jax.lax.dynamic_slice_in_dim(
            gtickets_of_all, rank * max_local_groups, max_local_groups
        )
        # (5) re-index local partials into global ticket space, then psum
        gacc = up.init_acc(max_groups, kind)
        merge_kind = {"count": "sum", "sum": "sum", "min": "min", "max": "max"}[kind]
        gacc = up.scatter_update(gacc, mine, lacc, kind=merge_kind)
        if merge_kind == "sum":
            gacc = jax.lax.psum(gacc, axis)
        elif merge_kind == "min":
            gacc = -jax.lax.pmax(-gacc, axis)
        else:
            gacc = jax.lax.pmax(gacc, axis)
        # saturation signal: a local table that overflowed max_local_groups
        # dropped keys BEFORE the union, so the global count alone cannot
        # see it — surface the sticky flags for the executor's policy check
        ovf = (ltable.overflowed | gtable.overflowed).astype(jnp.int32)
        ovf = jax.lax.psum(ovf, axis)
        return gacc, gtable.key_by_ticket, gtable.count, ovf

    vals = values if values is not None else jnp.ones_like(keys, dtype=jnp.float32)
    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axis), P(axis)),
        out_specs=(P(), P(), P(), P()),
        check_vma=False,  # while_loop carries start replicated (fresh table)
    )
    gacc, key_by_ticket, count, ovf = fn(keys, vals)
    return GroupByResult(key_by_ticket, up.finalize(kind, gacc), count), ovf


def partitioned_groupby_sharded(
    mesh,
    keys,
    values=None,
    *,
    kind: str = "count",
    max_groups: int,
    axis: str = "data",
    preagg_capacity: int = 4096,
    partition_capacity: int | None = None,
):
    """Leis-style partitioned aggregation across the mesh ``axis`` — adapter
    over ``GroupByPlan(strategy="sharded", shard_merge="all_to_all")``.
    Returns the legacy per-device layout ``(keys_p, vals_p, counts_p,
    overflow_p)`` (the executor's ``.raw``); the plan API's ``finalize``
    additionally offers the compacted single-table view."""
    from repro.engine.executors import make_executor
    from repro.engine.plan_api import (
        AggSpec,
        ExecutionPolicy,
        GroupByPlan,
        arrays_as_table,
    )

    table, _ = arrays_as_table(keys, values)
    agg = AggSpec("count") if kind == "count" else AggSpec(kind, "v")
    plan = GroupByPlan(
        keys=("__key__",), aggs=(agg,), strategy="sharded",
        max_groups=max_groups, saturation="unchecked", raw_keys=True,
        execution=ExecutionPolicy(
            mesh=mesh, axis=axis, shard_merge="all_to_all",
            preagg_capacity=preagg_capacity,
            partition_capacity=partition_capacity,
        ),
    )
    ex = make_executor(plan)
    ex.open()
    ex.consume(table)
    ex.finalize_raw()  # skips the unified-table compaction nothing here reads
    return ex.raw


def _partitioned_sharded_impl(
    mesh,
    keys,
    values=None,
    *,
    kind: str = "count",
    max_groups: int,
    axis: str = "data",
    preagg_capacity: int = 4096,
    partition_capacity: int | None = None,
):
    """Mesh protocol for the partitioned strategy (executor backend) with a
    real all_to_all exchange.

    Per device: morsel-vectorized pre-aggregation into a fixed table, spills
    kept raw; entries+spills are bucketed by partition id (hash >> bits) into
    fixed-size per-partition buckets; ``all_to_all`` delivers each partition
    to its owner; owners finish with a sort-segment aggregation of their
    partitions.  Bucket overflow (static shapes!) drops are prevented by
    sizing ``partition_capacity`` ≥ 2× expected per-partition load; the
    overflow count is returned so callers/tests can assert it is zero.
    """
    ndev = mesh.shape[axis]

    def local(kk, vv):
        kk = kk.reshape(-1)
        vv = vv.reshape(-1)
        st = make_preagg(preagg_capacity, kind)
        st, spill = preagg_morsel(st, kk, vv, kind)
        # rows to exchange: preagg entries + spilled raw rows
        ek, ev, ec = st.keys, st.vals, st.cnts
        sk = jnp.where(spill, kk, EMPTY_KEY)
        if kind == "count":
            sv = jnp.where(spill, 1.0, 0.0)
        elif kind == "sum":
            sv = jnp.where(spill, vv, 0.0)
        else:
            sv = jnp.where(spill, vv, up.neutral(kind))
        allk = jnp.concatenate([ek, sk])
        allv = jnp.concatenate([ev, sv])

        # partition id by high hash bits (radix partition)
        pid = partition_hash(allk, ndev, seed=7)
        pid = jnp.where(allk == EMPTY_KEY, ndev, pid)

        cap = partition_capacity or (2 * allk.shape[0] // ndev)
        # stable bucket packing: sort by pid, then slice fixed buckets
        order = jnp.argsort(pid, stable=True)
        pk, pv, pp = (jnp.take(x, order) for x in (allk, allv, pid))
        # position within partition
        pos = jnp.arange(pk.shape[0]) - jnp.searchsorted(pp, pp, side="left")
        overflow = jnp.sum((pos >= cap) & (pp < ndev))
        dest = jnp.where((pos < cap) & (pp < ndev), pp * cap + pos, ndev * cap)
        bk = jnp.full((ndev * cap + 1,), EMPTY_KEY, jnp.uint32).at[dest].set(pk)[:-1]
        bv = jnp.full((ndev * cap + 1,), up.neutral(kind), jnp.float32).at[dest].set(pv)[:-1]
        bk = bk.reshape(ndev, cap)
        bv = bv.reshape(ndev, cap)
        # the exchange
        xk = jax.lax.all_to_all(bk, axis, split_axis=0, concat_axis=0, tiled=False)
        xv = jax.lax.all_to_all(bv, axis, split_axis=0, concat_axis=0, tiled=False)
        xk = xk.reshape(-1)
        xv = xv.reshape(-1)
        # final partition-wise aggregation (owner side)
        tickets, key_by_ticket, cnt = tk.sort_ticketing(xk)
        acc = up.init_acc(max_groups, kind)
        merge_kind = {"count": "sum", "sum": "sum", "min": "min", "max": "max"}[kind]
        acc = up.sort_segment_update(acc, tickets, xv, kind=merge_kind)
        return (
            key_by_ticket[:max_groups],
            up.finalize(kind, acc),
            cnt.reshape(1),
            overflow.reshape(1).astype(jnp.int32),
        )

    vals = values if values is not None else jnp.ones_like(keys, dtype=jnp.float32)
    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axis), P(axis)),
        out_specs=(P(axis), P(axis), P(axis), P(axis)),
        check_vma=False,
    )
    keys_p, vals_p, counts_p, overflow_p = fn(keys, vals)
    return keys_p, vals_p, counts_p, overflow_p
