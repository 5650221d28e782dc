"""Pallas TPU kernel: partial-aggregate update over ticketed morsels (§3.2).

The accumulator vector stays resident in VMEM across the morsel grid (same
persistence trick as the ticketing table) and each grid step folds one
morsel of (ticket, value) rows into it.  Two in-core strategies, selected
statically:

  * ``scatter``: VMEM scatter-accumulate — the atomic-update analogue.
    Duplicate tickets within the morsel serialize inside the scatter unit
    (TPU's form of contention).  Mosaic has no vector scatter, so this
    strategy runs in interpret mode only (``kernels.ops.TPU_REFUSALS``).
  * ``onehot``: ``one_hot(tickets)ᵀ @ values`` on the MXU — contention
    becomes dense systolic work; skew-immune; preferred for small G.

The *thread-local* strategy is not a kernel concern: it is this same kernel
run per device with the merge done by ``psum`` (core/distributed.py).

Layout: a morsel of M rows is laid out as ``(M/128, 128)`` — 128 rows per
lane-row — so a block is 8×128-tileable whenever ``M`` is a multiple of
1024 (or the whole input is one morsel).  The kernel folds the block one
lane-row at a time.

Grid/BlockSpecs:
  tickets : (N/128, 128) blocked (M/128, 128), VMEM
  values  : (N/128, 128) blocked (M/128, 128), VMEM
  acc     : (G,) constant block, VMEM (out, persistent across grid)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
_NEUTRAL = {"sum": 0.0, "count": 0.0, "min": float("inf"), "max": float("-inf")}


def _segment_kernel(tickets_ref, values_ref, acc_ref, *, kind: str, strategy: str):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        acc_ref[...] = jnp.full_like(acc_ref[...], _NEUTRAL[kind])

    g = acc_ref.shape[0]
    groups = jax.lax.broadcasted_iota(jnp.int32, (1, g), 1)

    def fold_row(r, acc):
        t = tickets_ref[r, :]
        v = values_ref[r, :]
        if kind == "count":
            v = jnp.ones_like(v)
        if strategy == "onehot":
            # MXU path: parked rows (ticket -1) get an all-zero one-hot row.
            onehot = t[:, None] == groups
            if kind in ("sum", "count"):
                return acc + jnp.dot(
                    onehot.astype(jnp.float32).T, v[:, None],
                    preferred_element_type=jnp.float32,
                )[:, 0]
            dense = jnp.where(onehot, v[:, None], _NEUTRAL[kind])
            red = jnp.min(dense, axis=0) if kind == "min" else jnp.max(dense, axis=0)
            return jnp.minimum(acc, red) if kind == "min" else jnp.maximum(acc, red)
        assert strategy == "scatter", strategy
        # VMEM scatter-accumulate; park invalid rows on slot g-1 with neutral v.
        ok = t >= 0
        tt = jnp.where(ok, t, g - 1)
        vv = jnp.where(ok, v, _NEUTRAL[kind])
        if kind in ("sum", "count"):
            return acc.at[tt].add(vv)
        if kind == "min":
            return acc.at[tt].min(vv)
        return acc.at[tt].max(vv)

    acc_ref[...] = jax.lax.fori_loop(0, tickets_ref.shape[0], fold_row, acc_ref[...])


@functools.partial(
    jax.jit,
    static_argnames=("num_groups", "kind", "strategy", "morsel_size", "interpret"),
)
def segment_agg_pallas(
    tickets: jnp.ndarray,
    values: jnp.ndarray,
    *,
    num_groups: int,
    kind: str = "sum",
    strategy: str = "scatter",
    morsel_size: int = 1024,
    interpret: bool,
):
    """Fold (tickets, values) rows into a dense (num_groups,) accumulator.

    tickets: (N,) int32, -1 rows ignored; values: (N,) f32.  ``N`` must be a
    multiple of ``morsel_size``, and ``morsel_size`` a multiple of 128.
    """
    n = tickets.shape[0]
    assert n % morsel_size == 0, "pad to a morsel multiple"
    assert morsel_size % LANES == 0, "morsel_size must be a multiple of 128"
    rows = morsel_size // LANES
    t2 = tickets.astype(jnp.int32).reshape(n // LANES, LANES)
    v2 = values.astype(jnp.float32).reshape(n // LANES, LANES)

    return pl.pallas_call(
        functools.partial(_segment_kernel, kind=kind, strategy=strategy),
        grid=(n // morsel_size,),
        in_specs=[
            pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((num_groups,), lambda i: (0,)),
        out_shape=jax.ShapeDtypeStruct((num_groups,), jnp.float32),
        interpret=interpret,
    )(t2, v2)
