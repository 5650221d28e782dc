"""Jitted public wrappers around the Pallas kernels.

``interpret`` defaults to auto: False on TPU backends, True elsewhere (the
CPU validation mode mandated for this container).  Padding to morsel
multiples uses the EMPTY sentinel, which both kernels treat as no-ops.

``groupby_pallas`` is the kernel-backed end-to-end concurrent aggregation
(ticket → segment update → materialize), the hot path used by the engine
when it runs on TPU.  ``make_scan_update_fn`` adapts the segment-update
kernel to the engine's scan-compiled consume pipeline, so the kernel route
is just another scan body (engine/groupby.py).  Note the kernels' ticket
path shares the core contract on overflow: tickets issued past
``max_groups`` have their ``key_by_ticket`` scatters dropped (mode="drop"),
so a returned ``count > max_groups`` means the materialization is truncated
— the engine surfaces this via ``TicketTable.overflowed`` and refuses to
finalize.  ``multi_block_ticket`` extends the key space beyond
one VMEM-resident table by radix-splitting the stream over independent
table blocks — tickets get a per-block base, so the global ticket space has
bounded gaps (≤ blocks · slack), exactly the fuzzy-ticketer contract.
"""
from __future__ import annotations

import functools
import warnings

import jax
import jax.numpy as jnp

from repro.core.hashing import EMPTY_KEY, slot_hash
from repro.kernels.segment_agg import LANES, segment_agg_pallas
from repro.kernels.ticket_hash import ticket_hash_pallas


def _auto_interpret(interpret: bool | None = None) -> bool:
    """THE interpret-mode rule for every kernel entry point: an explicit
    flag wins; otherwise compile for the chip on a TPU backend and run the
    Pallas interpreter everywhere else."""
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


#: Kernel routes the TPU compiler (Mosaic, jax 0.9.0, v5e) refuses, with its
#: own words.  The probe gathers and the claim/accumulate scatters over the
#: VMEM table are vector gathers/scatters, which Mosaic does not lower; these
#: routes run in interpret mode only.  ``scan_body`` compiles with the
#: ``onehot`` update and is refused with every other update (all of which
#: map to the kernel's ``scatter`` strategy).
TPU_REFUSALS = {
    "fused": "NotImplementedError: Only 2D gather is supported",
    "split": "NotImplementedError: Only 2D gather is supported",
    "scan_body": (
        "NotImplementedError: Unimplemented primitive in Pallas TPU lowering "
        "for KernelType.TC: scatter-add"
    ),
}


def backend_refusal(kernel: str | None, update: str | None = None) -> str | None:
    """The compiler's refusal of a kernel route on the current backend, or
    None when the route compiles there (every route runs off-TPU, in
    interpret mode)."""
    if jax.default_backend() != "tpu" or kernel not in TPU_REFUSALS:
        return None
    if kernel == "scan_body" and update == "onehot":
        return None
    return TPU_REFUSALS[kernel]


# Deprecation shims: the engine selects kernels through the single
# ``ExecutionPolicy.kernel`` policy; the direct kernel entry points keep
# working but warn ONCE per process (per alias) so sweeps/benches don't
# drown in repeats.  ``reset_deprecation_warnings`` re-arms them (tests).
_WARNED: set = set()


def _warn_once(name: str, message: str) -> None:
    if name in _WARNED:
        return
    _WARNED.add(name)
    warnings.warn(message, DeprecationWarning, stacklevel=3)


def reset_deprecation_warnings() -> None:
    _WARNED.clear()


def _pad_to(x: jnp.ndarray, multiple: int, fill):
    n = x.shape[0]
    rem = (-n) % multiple
    if rem == 0:
        return x
    return jnp.concatenate([x, jnp.full((rem,), fill, x.dtype)])


def _ticket(
    keys: jnp.ndarray,
    *,
    capacity: int,
    max_groups: int,
    morsel_size: int = 1024,
    interpret: bool | None = None,
):
    """Kernel-backed GET_OR_INSERT over a key column (any length) — the
    engine-internal entry (no deprecation warning).

    Contract: the returned ``count`` must be checked against ``max_groups``
    by the caller — tickets past the bound had their ``key_by_ticket``
    scatters dropped (truncated materialization)."""
    n = keys.shape[0]
    kp = _pad_to(keys.astype(jnp.uint32), morsel_size, EMPTY_KEY)
    tickets, tkeys, ttks, kbt, count = ticket_hash_pallas(
        kp, capacity=capacity, max_groups=max_groups,
        morsel_size=morsel_size, interpret=_auto_interpret(interpret),
    )
    return tickets[:n], kbt, count


def ticket(
    keys: jnp.ndarray,
    *,
    capacity: int,
    max_groups: int,
    morsel_size: int = 1024,
    interpret: bool | None = None,
):
    """DEPRECATED direct kernel call — select kernels through
    ``ExecutionPolicy.kernel`` (``"split"``/``"fused"``) or the
    :func:`groupby_kernel` front door instead."""
    _warn_once(
        "ticket",
        "kernels.ops.ticket is deprecated; select the kernel route via "
        "ExecutionPolicy.kernel ('split'/'fused') or groupby_kernel()",
    )
    return _ticket(
        keys, capacity=capacity, max_groups=max_groups,
        morsel_size=morsel_size, interpret=interpret,
    )


def _segment_aggregate(
    tickets: jnp.ndarray,
    values: jnp.ndarray,
    *,
    num_groups: int,
    kind: str = "sum",
    strategy: str = "scatter",
    morsel_size: int = 1024,
    interpret: bool | None = None,
):
    # one morsel at most the (lane-rounded) input: the kernel's block is
    # then 8x128-tileable or the whole array
    n = tickets.shape[0]
    morsel_size = min(morsel_size, -(-max(n, 1) // LANES) * LANES)
    tp = _pad_to(tickets.astype(jnp.int32), morsel_size, -1)
    vp = _pad_to(values.astype(jnp.float32), morsel_size, 0.0)
    return segment_agg_pallas(
        tp, vp, num_groups=num_groups, kind=kind, strategy=strategy,
        morsel_size=morsel_size, interpret=_auto_interpret(interpret),
    )


def segment_aggregate(
    tickets: jnp.ndarray,
    values: jnp.ndarray,
    *,
    num_groups: int,
    kind: str = "sum",
    strategy: str = "scatter",
    morsel_size: int = 1024,
    interpret: bool | None = None,
):
    """DEPRECATED direct kernel call — select kernels through
    ``ExecutionPolicy.kernel`` or the :func:`groupby_kernel` front door."""
    _warn_once(
        "segment_aggregate",
        "kernels.ops.segment_aggregate is deprecated; select the kernel "
        "route via ExecutionPolicy.kernel ('split'/'fused') or "
        "groupby_kernel()",
    )
    return _segment_aggregate(
        tickets, values, num_groups=num_groups, kind=kind, strategy=strategy,
        morsel_size=morsel_size, interpret=interpret,
    )


@functools.lru_cache(maxsize=None)
def make_scan_update_fn(
    *,
    strategy: str = "scatter",
    morsel_size: int = 1024,
    interpret: bool | None = None,
):
    """Adapt the Pallas segment-update kernel to the engine's update-fn
    signature ``(acc, tickets, values, kind=...) -> acc``.

    The engine's scan-compiled consume pipeline threads its accumulators
    through ``lax.scan``; with this adapter the kernel folds each ticketed
    morsel into a fresh partial vector in VMEM which is then merged into the
    carried accumulator — making the kernel route just another scan body
    instead of a separate host-driven code path.  Memoized so every operator
    with the same (strategy, morsel_size, interpret) shares one function
    object — the engine jit-specializes its scan on update-fn identity, and
    a fresh closure per operator would recompile the whole consume scan.
    """

    def update_fn(acc, tickets, values, kind: str = "sum"):
        part = _segment_aggregate(
            tickets, values, num_groups=acc.shape[0], kind=kind,
            strategy=strategy, morsel_size=morsel_size, interpret=interpret,
        )
        if kind in ("sum", "count"):
            return acc + part.astype(acc.dtype)
        # min/max: the kernel leaves ±inf identities for untouched groups,
        # which lose against any carried value under minimum/maximum.
        part = part.astype(acc.dtype)
        return jnp.minimum(acc, part) if kind == "min" else jnp.maximum(acc, part)

    return update_fn


def groupby_kernel(
    keys: jnp.ndarray,
    values: jnp.ndarray | None = None,
    *,
    kind: str = "count",
    max_groups: int,
    capacity: int | None = None,
    morsel_size: int = 1024,
    update_strategy: str = "scatter",
    interpret: bool | None = None,
    saturation: str = "raise",
    fused: bool = False,
    programs: int = 1,
):
    """THE kernel front door: single-aggregate kernel-backed GROUP BY over
    raw arrays (paper Fig. 2 end-to-end), running behind the executor seam
    with ``ExecutionPolicy.kernel`` doing the selection.

    ``fused=False`` runs the split ticket + segment-aggregate route
    (``kernel="split"``); ``fused=True`` streams through the single
    VMEM-resident fused kernel (``kernel="fused"``), with ``programs``
    per-grid-program local tables merged at the boundary.  Engine callers
    should construct a :class:`~repro.engine.plan_api.GroupByPlan` and set
    ``execution.kernel`` directly; this wrapper exists for direct kernel
    users and benches.
    """
    from repro.engine.plan_api import (
        AggSpec,
        ExecutionPolicy,
        GroupByPlan,
        arrays_as_table,
        execute,
    )

    table, _ = arrays_as_table(keys, values)
    agg = AggSpec("count") if kind == "count" else AggSpec(kind, "v")
    plan = GroupByPlan(
        keys=("__key__",), aggs=(agg,), strategy="concurrent",
        max_groups=max_groups, saturation=saturation, raw_keys=True,
        execution=ExecutionPolicy(
            kernel="fused" if fused else "split", kernel_programs=programs,
            capacity=capacity, morsel_size=morsel_size,
            update=update_strategy, interpret=interpret,
        ),
    )
    out = execute(plan, table)
    return out["key"], out[agg.name], out["__num_groups__"][0]


def groupby_pallas(
    keys: jnp.ndarray,
    values: jnp.ndarray | None = None,
    *,
    kind: str = "count",
    max_groups: int,
    capacity: int | None = None,
    morsel_size: int = 1024,
    update_strategy: str = "scatter",
    interpret: bool | None = None,
    raise_on_overflow: bool = True,
    saturation: str | None = None,
):
    """DEPRECATED legacy adapter (the pre-``kernel=`` spelling of the split
    kernel route) — use :func:`groupby_kernel` or a plan with
    ``ExecutionPolicy.kernel="split"``.  Signature-compatible: behaves
    exactly like ``groupby_kernel(..., fused=False)``.

    ``raise_on_overflow`` (default) maps to ``saturation="raise"``: the
    returned ticket count is checked against ``max_groups`` on the host and
    an error is raised when the stream held more distinct keys — the
    kernel's ``key_by_ticket``/acc scatters past the bound are dropped, so
    the materialization would otherwise be silently truncated.  Pass False
    (= ``saturation="unchecked"``) to skip the blocking device sync this
    costs, or ``saturation="grow"`` to recover with a grown bound.
    """
    _warn_once(
        "groupby_pallas",
        "kernels.ops.groupby_pallas is deprecated; use groupby_kernel() or "
        "a GroupByPlan with ExecutionPolicy.kernel='split'",
    )
    if saturation is None:
        saturation = "raise" if raise_on_overflow else "unchecked"
    return groupby_kernel(
        keys, values, kind=kind, max_groups=max_groups, capacity=capacity,
        morsel_size=morsel_size, update_strategy=update_strategy,
        interpret=interpret, saturation=saturation, fused=False,
    )


def multi_block_ticket(
    keys: jnp.ndarray,
    *,
    blocks: int,
    capacity_per_block: int,
    max_groups_per_block: int,
    morsel_size: int = 1024,
    interpret: bool | None = None,
):
    """Radix-split ticketing for key spaces larger than one VMEM table.

    Key stream is partitioned by high hash bits into ``blocks`` sub-streams,
    each ticketed against its own VMEM-sized table; global ticket = block ·
    max_groups_per_block + local ticket.  Gaps are bounded by blocks·slack
    (fuzzy-ticketer contract); materialization compacts them.
    """
    assert blocks & (blocks - 1) == 0
    n = keys.shape[0]
    kb = keys.astype(jnp.uint32)
    bid = slot_hash(kb, blocks, seed=13)
    out_tickets = jnp.full((n,), -1, jnp.int32)
    kbts, counts = [], []
    for b in range(blocks):
        sel = bid == b
        # static-shape per-block stream: mask non-members to EMPTY
        kblock = jnp.where(sel, kb, EMPTY_KEY)
        tb, kbt_b, cnt_b = _ticket(
            kblock, capacity=capacity_per_block,
            max_groups=max_groups_per_block,
            morsel_size=morsel_size, interpret=interpret,
        )
        out_tickets = jnp.where(sel, tb + b * max_groups_per_block, out_tickets)
        kbts.append(kbt_b)
        counts.append(cnt_b)
    return out_tickets, jnp.concatenate(kbts), jnp.stack(counts)
