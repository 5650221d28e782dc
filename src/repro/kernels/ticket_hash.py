"""Pallas TPU kernel: Folklore* GET_OR_INSERT ticketing (paper Algorithm 1).

TPU-native design
-----------------
The CPU Folklore* table lives in cache-coherent DRAM and threads race with a
single-word CAS.  On TPU we keep the table **resident in VMEM** across the
whole morsel stream: the grid iterates over morsels (the paper's unit of
vectorized execution), and the table/count/key-list outputs use constant
index maps so the same VMEM block persists from step to step — the "global"
hash table, scoped to a core.

Within a morsel, the (8,128) VPU lanes are the "threads".  The single-word
CAS becomes a **claim round**: every unresolved lane scatter-writes its lane
id into a claim array at its probe slot (associative ``min`` ⇒ deterministic
winner), reads the slot back, and the winner publishes its (key, ticket)
pair.  Losers retry; a loser whose key was just published hits the fast-path
lookup on the next round — byte-for-byte the control flow of Algorithm 1.

The **fuzzy ticketer** (paper Fig. 3) maps to a scalar ticket base carried in
SMEM: each claim round allocates the range ``[base, base + winners)`` with a
dense prefix-sum rank — one scalar bump per round instead of one contended
FETCH_ADD per insert, and gap-free by construction here (the functional
equivalent of range-claiming without wasted range tails).

Sizing: table capacity C must be a power of two with C·8B + morsel·12B well
under VMEM (≤ 2^17 slots ⇒ ≤ 1 MiB for keys+tickets).  Larger key spaces are
handled above this kernel by radix-splitting the key stream over multiple
table blocks (see ops.multi_block_ticket) — the TPU version of the paper's
observation that the table must fit the cache hierarchy to scale.

Grid/BlockSpecs:
  keys    : (num_morsels, M)  blocked (1, M), VMEM
  tickets : (num_morsels, M)  blocked (1, M), VMEM (out)
  table_keys/table_tickets : (C,) constant block, VMEM (out, persistent)
  key_by_ticket : (G,) constant block, VMEM (out, persistent)
  count   : (1,) SMEM (out, persistent)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.hashing import EMPTY_KEY

# int32 view of the uint32 EMPTY sentinel (Mosaic prefers int32 vectors).
# Kept as a Python int so the kernel body doesn't capture a traced constant.
EMPTY_I32 = -1  # int32 bit pattern of 0xFFFFFFFF


def _slot_hash_i32(keys: jnp.ndarray, capacity: int) -> jnp.ndarray:
    """xxhash-style avalanche on the int32 bit pattern, masked to capacity.
    Matches core.hashing.slot_hash(seed=0) bit-for-bit (same constants)."""
    x = keys.astype(jnp.uint32)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x85EBCA77)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE3D)
    x = x ^ (x >> 16)
    return (x & jnp.uint32(capacity - 1)).astype(jnp.int32)


def _ticket_kernel(
    keys_ref,          # (1, M) int32 in VMEM
    tickets_ref,       # (1, M) int32 out
    tkeys_ref,         # (C,) int32 out, persistent
    ttks_ref,          # (C,) int32 out, persistent
    kbt_ref,           # (G,) int32 out, persistent
    count_ref,         # (1,) int32 out, SMEM, persistent
    *,
    capacity: int,
):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        tkeys_ref[...] = jnp.full_like(tkeys_ref[...], EMPTY_I32)
        ttks_ref[...] = jnp.zeros_like(ttks_ref[...])
        kbt_ref[...] = jnp.full_like(kbt_ref[...], EMPTY_I32)
        count_ref[0] = 0

    keys = keys_ref[0, :]
    m = keys.shape[0]
    lane = jax.lax.iota(jnp.int32, m)
    valid = keys != EMPTY_I32
    slot0 = _slot_hash_i32(keys, capacity)

    tkeys = tkeys_ref[...]
    ttks = ttks_ref[...]
    kbt = kbt_ref[...]
    base = count_ref[0]
    g = kbt.shape[0]
    # Bounded probe loop (same contract as core.ticketing.get_or_insert):
    # a completely full table must terminate, not spin; unresolved lanes
    # surface as ticket -1 and the caller checks count against max_groups.
    max_rounds = 2 * capacity + 2

    def cond(st):
        return jnp.any(st[4]) & (st[7] < max_rounds)

    def body(st):
        tkeys, ttks, kbt, slot, active, out, count, rounds = st
        probed_key = jnp.take(tkeys, slot)
        probed_tk = jnp.take(ttks, slot)

        # Algorithm 1 fast path: published slot with matching key.
        hit = active & (probed_tk != 0) & (probed_key == keys)
        out = jnp.where(hit, probed_tk, out)
        active = active & ~hit

        # Occupied by a different key: linear probe forward.
        collide = active & (probed_tk != 0) & (probed_key != keys)
        slot = jnp.where(collide, (slot + 1) & (capacity - 1), slot)

        # Claim round — CAS analogue (scatter-min vote + readback).  Lanes
        # that are not claiming park on an out-of-bounds index; mode="drop"
        # makes the scatter a true no-op for them (no clobber races).
        trying = active & (probed_tk == 0)
        claim_slot = jnp.where(trying, slot, capacity)
        claims = jnp.full((capacity,), m, jnp.int32)
        claims = claims.at[claim_slot].min(lane, mode="drop")
        won = trying & (jnp.take(claims, slot) == lane)

        # Fuzzy-ticketer range for this round (1-based tickets).
        rank = jnp.cumsum(won.astype(jnp.int32)) - 1
        new_ticket = count + 1 + rank
        pub_slot = jnp.where(won, slot, capacity)  # OOB park → dropped
        tkeys = tkeys.at[pub_slot].set(keys, mode="drop")
        ttks = ttks.at[pub_slot].set(new_ticket, mode="drop")
        kbt_idx = jnp.where(won, new_ticket - 1, g)
        kbt = kbt.at[kbt_idx].set(keys, mode="drop")

        out = jnp.where(won, new_ticket, out)
        active = active & ~won
        count = count + jnp.sum(won.astype(jnp.int32))
        return tkeys, ttks, kbt, slot, active, out, count, rounds + 1

    init = (
        tkeys, ttks, kbt, slot0, valid, jnp.zeros((m,), jnp.int32), base,
        jnp.zeros((), jnp.int32),
    )
    tkeys, ttks, kbt, _, _, out, count, _ = jax.lax.while_loop(cond, body, init)

    tkeys_ref[...] = tkeys
    ttks_ref[...] = ttks
    kbt_ref[...] = kbt
    count_ref[0] = count
    # unresolved lanes (saturated table) still have out == 0 → ticket -1
    tickets_ref[0, :] = jnp.where(valid & (out > 0), out - 1, -1)


@functools.partial(
    jax.jit, static_argnames=("capacity", "max_groups", "morsel_size", "interpret")
)
def ticket_hash_pallas(
    keys: jnp.ndarray,
    *,
    capacity: int,
    max_groups: int,
    morsel_size: int = 1024,
    interpret: bool,
):
    """Run the ticketing kernel over a key column.

    Args:
      keys: (N,) uint32/int32; N must be a multiple of morsel_size (pad with
        EMPTY_KEY).
      capacity: table slots (pow2, ≤ 2^17 to stay in VMEM).
      max_groups: bound on unique keys (key_by_ticket length).
      interpret: run in Pallas interpret mode.  The TPU compiler refuses
        this kernel (``kernels.ops.TPU_REFUSALS``), so only True runs.

    Returns (tickets (N,) int32 0-based, table_keys, table_tickets,
    key_by_ticket (uint32), count ()).
    """
    assert capacity & (capacity - 1) == 0
    n = keys.shape[0]
    assert n % morsel_size == 0, "pad keys to a morsel multiple"
    num_morsels = n // morsel_size
    keys2 = keys.astype(jnp.uint32).astype(jnp.int32).reshape(num_morsels, morsel_size)

    out_shapes = (
        jax.ShapeDtypeStruct((num_morsels, morsel_size), jnp.int32),  # tickets
        jax.ShapeDtypeStruct((capacity,), jnp.int32),                 # table keys
        jax.ShapeDtypeStruct((capacity,), jnp.int32),                 # table tickets
        jax.ShapeDtypeStruct((max_groups,), jnp.int32),               # key_by_ticket
        jax.ShapeDtypeStruct((1,), jnp.int32),                        # count
    )
    grid = (num_morsels,)
    tickets, tkeys, ttks, kbt, count = pl.pallas_call(
        functools.partial(_ticket_kernel, capacity=capacity),
        grid=grid,
        in_specs=[pl.BlockSpec((1, morsel_size), lambda i: (i, 0))],
        out_specs=(
            pl.BlockSpec((1, morsel_size), lambda i: (i, 0)),
            pl.BlockSpec((capacity,), lambda i: (0,)),
            pl.BlockSpec((capacity,), lambda i: (0,)),
            pl.BlockSpec((max_groups,), lambda i: (0,)),
            pl.BlockSpec(memory_space=pltpu.SMEM, block_shape=(1,), index_map=lambda i: (0,)),
        ),
        out_shape=out_shapes,
        interpret=interpret,
    )(keys2)
    return (
        tickets.reshape(n),
        tkeys.astype(jnp.uint32),
        ttks,
        kbt.astype(jnp.uint32),
        count[0],
    )
