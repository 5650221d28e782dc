"""Ticketing: map each unique key to a dense integer "ticket".

This is the paper's §3.1 contribution, adapted to TPU SIMD semantics.  The
CPU implementation resolves insert races with a single-word CAS (Folklore*,
Algorithm 1).  A TPU core has no CAS, but it has deterministic associative
scatters: ``table.at[slots].min(lane_id)`` lets every lane "claim" a slot and
the readback decides a unique winner per slot.  Losers simply retry, and —
exactly as in Folklore* — the retry hits the fast-path lookup because the
winner has already published its (key, ticket) pair.  This file is the pure
functional reference; ``repro.kernels.ticket_hash`` is the Pallas kernel with
the same protocol and a VMEM-resident table.

Ticket values: tickets are issued per claim-round as ``base + rank`` where
``rank`` is the winner's prefix rank in that round (a dense cumsum).  This is
the TPU analogue of the paper's *fuzzy ticketer*: a contended FETCH_ADD per
insert is replaced by one range claim per round.  In this functional
implementation the ranges are exact, so tickets are gap-free; the Pallas
kernel claims one range per morsel and may leave bounded gaps (≤ morsels),
which materialization compacts (§3.1 "the number of gaps is bounded linearly
by the number of threads").

Tickets are **1-based** internally: ticket 0 is the reserved empty sentinel,
matching the paper's single-word-CAS trick.  Public APIs return 0-based
tickets.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.hashing import EMPTY_KEY, slot_hash


class TicketTable(NamedTuple):
    """Functional state of the ticketing hash table.

    Attributes:
      keys:    (capacity,) uint32 — stored keys, EMPTY_KEY where unoccupied.
      tickets: (capacity,) int32  — 1-based tickets, 0 where unoccupied.
      key_by_ticket: (max_groups,) uint32 — keys in ticket order (the paper's
        ticket-ordered key copy used for materialization).
      count:   () int32 — number of tickets issued so far (next base).
      overflowed: () bool — sticky: tickets were issued past ``max_groups``,
        so their ``key_by_ticket`` (and any ticket-indexed accumulator)
        scatters dropped.  Once set, materialized results are truncated and
        the engine refuses to finalize.
    """

    keys: jnp.ndarray
    tickets: jnp.ndarray
    key_by_ticket: jnp.ndarray
    count: jnp.ndarray
    overflowed: jnp.ndarray

    @property
    def capacity(self) -> int:
        return self.keys.shape[0]

    @property
    def max_groups(self) -> int:
        return self.key_by_ticket.shape[0]


def make_table(capacity: int, max_groups: int | None = None) -> TicketTable:
    """Allocate an empty ticketing table. ``capacity`` must be a power of two
    and should be ≥ 2× the expected number of unique keys (load factor ≤ .5,
    the regime in which linear probing's expected probe count is O(1))."""
    assert capacity & (capacity - 1) == 0, "capacity must be a power of 2"
    if max_groups is None:
        max_groups = capacity
    return TicketTable(
        keys=jnp.full((capacity,), EMPTY_KEY, dtype=jnp.uint32),
        tickets=jnp.zeros((capacity,), dtype=jnp.int32),
        key_by_ticket=jnp.full((max_groups,), EMPTY_KEY, dtype=jnp.uint32),
        count=jnp.zeros((), dtype=jnp.int32),
        overflowed=jnp.zeros((), dtype=jnp.bool_),
    )


# get_or_insert and lookup are jitted: called eagerly (the executors' host
# merges and routing probes), the probe loop would close over the concrete
# keys, embed them as constants and compile anew on every call.
@functools.partial(jax.jit, static_argnames=("seed", "count_probes"))
def get_or_insert(table: TicketTable, keys: jnp.ndarray, *, seed: int = 0,
                  count_probes: bool = False):
    """Vectorized GET_OR_INSERT over a morsel of keys (paper Algorithm 1).

    Returns ``(tickets, new_table)`` where ``tickets`` is int32 of the same
    shape as ``keys`` holding the 0-based ticket of each key.  Rows whose key
    equals EMPTY_KEY get ticket -1 (the paper returns the sentinel 0; we keep
    sentinel handling out-of-band so downstream masks are explicit).

    ``count_probes=True`` additionally threads a per-lane probe-length
    counter (number of slot inspections until the lane resolved; 0 for
    sentinel lanes, the loop bound for saturated lanes) and returns
    ``(tickets, new_table, probe_len)``.  The counter rides the existing
    while-loop carry, so enabling it adds no extra passes; the default
    ``False`` path traces exactly as before.

    The loop invariant mirrors Algorithm 1 exactly:
      * occupied slot with matching key  → fast-path lookup hit;
      * occupied slot with different key → advance (linear probe);
      * empty slot                       → claim round (CAS analogue);
    with the one TPU twist that claims from all lanes resolve simultaneously
    via scatter-min + readback instead of a per-lane CAS.

    Scan-body safety: the probe loop is bounded, so the function terminates
    even on a completely full table.  A lane that exhausts the bound (probe
    table saturated — no reachable empty slot) returns ticket -1 *without*
    having been inserted; callers detect this as ``(tickets < 0) & (keys !=
    EMPTY_KEY)`` and recover by migrating to a bigger table and replaying the
    morsel (inserts already published are idempotent under replay: the retry
    takes the fast-path lookup and issues no new ticket).  Tickets issued
    past ``max_groups`` set the sticky ``overflowed`` flag: their
    ``key_by_ticket`` scatters dropped, so the table's materialization is
    truncated and the engine refuses to finalize.
    """
    flat = keys.reshape(-1).astype(jnp.uint32)
    n = flat.shape[0]
    capacity = table.capacity
    mask = capacity - 1
    lane = jnp.arange(n, dtype=jnp.int32)
    # One wrap of linear probing plus one claim round per possible winner —
    # past this, remaining lanes provably face a saturated table.
    max_rounds = 2 * capacity + 2

    valid = flat != EMPTY_KEY
    slot0 = slot_hash(flat, capacity, seed=seed)

    def cond(state):
        active, rounds = state[4], state[7]
        return jnp.any(active) & (rounds < max_rounds)

    def body(state):
        if count_probes:
            tkeys, ttks, kbt, slot, active, out, count, rounds, probe_len = state
            # Each active lane inspects exactly one slot per iteration.
            probe_len = probe_len + active.astype(jnp.int32)
        else:
            tkeys, ttks, kbt, slot, active, out, count, rounds = state
        probed_key = jnp.take(tkeys, slot)
        probed_tk = jnp.take(ttks, slot)

        # Fast-path lookup: slot published (ticket != 0) and key matches.
        hit = active & (probed_tk != 0) & (probed_key == flat)
        out = jnp.where(hit, probed_tk, out)
        active = active & ~hit

        # Occupied by a different, published key → linear probe forward.
        # (A slot with ticket==0 is empty; Folklore* writes ticket first via
        # CAS, we publish (key, ticket) atomically per round, so ticket==0
        # ⟺ key==EMPTY_KEY here and the "k = EmptyKey → continue" spin path
        # of Algorithm 1 cannot occur.)
        collide = active & (probed_tk != 0) & (probed_key != flat)
        slot = jnp.where(collide, (slot + 1) & mask, slot)

        # Claim round on empty slots: scatter-min of lane id, readback votes.
        # Non-claiming lanes park on an out-of-bounds index; mode="drop"
        # makes their scatter a true no-op (same idiom as the Pallas kernel).
        trying = active & (probed_tk == 0)
        claim_slot = jnp.where(trying, slot, capacity)
        claims = jnp.full((capacity,), n, dtype=jnp.int32)
        claims = claims.at[claim_slot].min(lane, mode="drop")
        won = trying & (jnp.take(claims, slot) == lane)

        # Fuzzy-ticketer range for this round: base=count, winner ranks.
        rank = jnp.cumsum(won.astype(jnp.int32)) - 1
        new_ticket = count + 1 + rank  # 1-based
        ticket_w = jnp.where(won, new_ticket, 0)

        # Publish winners' (key, ticket); park losers for retry (they will
        # re-gather this slot next round and take the fast path on a match).
        pub_slot = jnp.where(won, slot, capacity)
        tkeys = tkeys.at[pub_slot].set(flat, mode="drop")
        ttks = ttks.at[pub_slot].set(ticket_w, mode="drop")

        # Ticket-ordered key copy (materialization support).  A winner whose
        # ticket lands past max_groups is dropped here — detected below.
        kbt_idx = jnp.where(won, new_ticket - 1, kbt.shape[0])
        kbt = kbt.at[kbt_idx].set(flat, mode="drop")

        out = jnp.where(won, new_ticket, out)
        active = active & ~won
        count = count + jnp.sum(won.astype(jnp.int32))
        if count_probes:
            return tkeys, ttks, kbt, slot, active, out, count, rounds + 1, probe_len
        return tkeys, ttks, kbt, slot, active, out, count, rounds + 1

    init = (
        table.keys,
        table.tickets,
        table.key_by_ticket,
        slot0,
        valid,
        jnp.zeros((n,), dtype=jnp.int32),
        table.count,
        jnp.zeros((), dtype=jnp.int32),
    )
    if count_probes:
        init = init + (jnp.zeros((n,), dtype=jnp.int32),)
        tkeys, ttks, kbt, _, _, out, count, _, probe_len = jax.lax.while_loop(
            cond, body, init
        )
    else:
        tkeys, ttks, kbt, _, _, out, count, _ = jax.lax.while_loop(cond, body, init)
    # Unresolved lanes (saturated table) still have out == 0 → ticket -1.
    tickets = jnp.where(valid & (out > 0), out - 1, -1).reshape(keys.shape)
    overflowed = table.overflowed | (count > table.max_groups)
    new_table = TicketTable(tkeys, ttks, kbt, count, overflowed)
    if count_probes:
        return tickets, new_table, probe_len.reshape(keys.shape)
    return tickets, new_table


@functools.partial(jax.jit, static_argnames=("seed",))
def lookup(table: TicketTable, keys: jnp.ndarray, *, seed: int = 0) -> jnp.ndarray:
    """Read-only probe (the contention-free fast path). Returns 0-based
    tickets, -1 for absent or sentinel keys."""
    flat = keys.reshape(-1).astype(jnp.uint32)
    capacity = table.capacity
    mask = capacity - 1
    slot0 = slot_hash(flat, capacity, seed=seed)
    valid = flat != EMPTY_KEY

    def cond(state):
        _, active, _ = state
        return jnp.any(active)

    def body(state):
        slot, active, out = state
        probed_key = jnp.take(table.keys, slot)
        probed_tk = jnp.take(table.tickets, slot)
        hit = active & (probed_tk != 0) & (probed_key == flat)
        miss = active & (probed_tk == 0)
        out = jnp.where(hit, probed_tk - 1, out)
        active = active & ~hit & ~miss
        slot = jnp.where(active, (slot + 1) & mask, slot)
        return slot, active, out

    _, _, out = jax.lax.while_loop(
        cond, body, (slot0, valid, jnp.full(flat.shape, -1, jnp.int32))
    )
    return jnp.where(valid, out, -1).reshape(keys.shape)


def sort_ticketing(keys: jnp.ndarray):
    """Sort-based ticketing baseline (no hash table at all).

    Sort keys, detect uniques by adjacent comparison, ticket = prefix-count.
    O(n log n) but branch-free and fully dense — on TPU this is the natural
    competitor to the hash table, and it doubles as the oracle in tests.
    Returns (tickets, key_by_ticket, count); sentinel rows get ticket -1 and
    sort to the end (EMPTY_KEY is the max uint32).
    """
    flat = keys.reshape(-1).astype(jnp.uint32)
    n = flat.shape[0]
    order = jnp.argsort(flat)
    skeys = jnp.take(flat, order)
    valid_s = skeys != EMPTY_KEY
    is_new = valid_s & jnp.concatenate(
        [jnp.ones((1,), bool), skeys[1:] != skeys[:-1]]
    )
    ticket_s = jnp.cumsum(is_new.astype(jnp.int32)) - 1
    count = jnp.sum(is_new.astype(jnp.int32))
    tickets = jnp.zeros((n,), jnp.int32).at[order].set(
        jnp.where(valid_s, ticket_s, -1)
    )
    key_by_ticket = (
        jnp.full((n,), EMPTY_KEY, jnp.uint32)
        .at[jnp.where(is_new, ticket_s, n - 1)]
        .set(jnp.where(is_new, skeys, EMPTY_KEY))
    )
    return tickets.reshape(keys.shape), key_by_ticket, count


def direct_ticketing(keys: jnp.ndarray, domain: int):
    """Perfect-hash ticketing for a bounded key domain (paper §3.1 closing
    discussion, Gaffney & Patel): ticket == key. Used for e.g. MoE expert
    ids where the domain is tiny and known."""
    flat = keys.reshape(-1).astype(jnp.int32)
    tickets = jnp.where((flat >= 0) & (flat < domain), flat, -1)
    key_by_ticket = jnp.arange(domain, dtype=jnp.uint32)
    return tickets.reshape(keys.shape), key_by_ticket, jnp.int32(domain)
