#!/usr/bin/env python3
"""Smoke run of the GROUP BY engine on a TPU, end to end, in one process.

    python chip_smoke.py [--rows N] [--seed S]       # one chip, queries Q1-Q7
    python chip_smoke.py --chips 4 [--rows N]        # sharded path on 4 chips

Data follows the paper's §4.1 shapes (``benchmarks.common.gen_keys``: low =
1,000 keys, high = N/10; uniform, zipf, or one heavy key holding 50 %),
with a ``qty`` column (integers 1-9 stored as float32) and a ``price``
column (float32 cents in [1, 1000)).  The default 6M rows is about TPC-H
SF1 lineitem (6,001,215 rows); the whole one-chip run, compilation
included, stays well inside 20 minutes there.  Each table is placed on the
device once and streamed through ``Scan(table, chunk_rows=1<<20)`` into
``GroupByPlan.stream(...).result()`` (what ``GroupByPlan.collect`` runs) or
an ``AggregationServer``.  Every result is checked against a float64 NumPy
GROUP BY written here: COUNT, MIN, MAX and SUM(qty) exactly (every group sum
stays below 2^24, which float32 accumulators hold exactly), MEAN(price)
within ``MEAN_RTOL``.

Earlier lines give the device, then one line per query: the route the
executor ran, rows, groups, largest error, and cold (compile included) and
warm seconds — observations, not benchmark metrics.  Any mismatch, failed
query or exception exits non-zero.  The last line, printed only on success,
is ``{"ok": true, "device": {"platform", "kind", "count"}}``.  Without a TPU
the script exits 2 before running anything.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [REPO, os.path.join(REPO, "src")]

from benchmarks.common import gen_keys  # noqa: E402
from repro.engine.groupby import AggSpec  # noqa: E402

MEAN_RTOL = 1e-4
CHUNK_ROWS = 1 << 20

COUNT = AggSpec("count")
SUM_QTY = AggSpec("sum", "qty")


class Mismatch(AssertionError):
    """The engine's answer differs from the NumPy reference."""


# ---------------------------------------------------------------------------
# data and the plain NumPy reference


def make_columns(rows: int, card: str, dist: str, seed: int) -> dict:
    """Host columns of one §4.1 dataset, generated from ``seed``."""
    rng = np.random.default_rng([seed, 1])
    return {
        "key": gen_keys(rows, card, dist, seed=seed),
        "qty": rng.integers(1, 10, rows).astype(np.float32),
        "price": (rng.integers(100, 100_000, rows) / 100).astype(np.float32),
    }


def hashed_key(key: np.ndarray) -> np.ndarray:
    """The key the engine reports for a one-column GROUP BY: the murmur3
    32-bit finalizer of the value plus the golden-ratio constant (mod 2^32),
    with the reserved 0xFFFFFFFF remapped to 0x7FFFFFFF."""
    x = key.astype(np.uint32)
    x ^= x >> np.uint32(16)
    x *= np.uint32(0x85EBCA6B)
    x ^= x >> np.uint32(13)
    x *= np.uint32(0xC2B2AE35)
    x ^= x >> np.uint32(16)
    x += np.uint32(0x9E3779B9)
    x[x == np.uint32(0xFFFFFFFF)] = np.uint32(0x7FFFFFFF)
    return x


def reference(cols: dict, aggs) -> dict:
    """float64 GROUP BY hashed_key(key): sorted keys plus one array per
    aggregate name."""
    uniq, inv = np.unique(hashed_key(cols["key"]), return_inverse=True)
    count = np.zeros(uniq.size)
    np.add.at(count, inv, 1.0)
    out = {"key": uniq}
    for a in aggs:
        if a.kind == "count":
            out[a.name] = count
            continue
        v = cols[a.column].astype(np.float64)
        if a.kind in ("sum", "mean"):
            acc = np.zeros(uniq.size)
            np.add.at(acc, inv, v)
            out[a.name] = acc if a.kind == "sum" else acc / count
        elif a.kind == "min":
            acc = np.full(uniq.size, np.inf)
            np.minimum.at(acc, inv, v)
            out[a.name] = acc
        else:
            acc = np.full(uniq.size, -np.inf)
            np.maximum.at(acc, inv, v)
            out[a.name] = acc
    return out


def compare(got: dict, ref: dict, aggs) -> float:
    """Check an engine result (host arrays: ``key``, ``__num_groups__`` and
    one column per aggregate) against :func:`reference`.  Returns the
    largest error seen (absolute, or relative for MEAN); raises
    :class:`Mismatch` past the contract."""
    n = int(got["__num_groups__"][0])
    keys = np.asarray(got["key"][:n])
    order = np.argsort(keys)
    if n != ref["key"].size or not np.array_equal(keys[order], ref["key"]):
        raise Mismatch(
            f"group keys differ: engine has {n} groups, reference "
            f"{ref['key'].size}"
        )
    worst = 0.0
    for a in aggs:
        g = np.asarray(got[a.name][:n], np.float64)[order]
        r = ref[a.name]
        if a.kind == "mean":
            err = float(np.max(np.abs(g - r) / np.maximum(np.abs(r), 1e-30)))
            ok = err <= MEAN_RTOL
        else:
            err = float(np.max(np.abs(g - r)))
            ok = err == 0.0
        if not ok:
            bad = int(np.argmax(np.abs(g - r)))
            raise Mismatch(
                f"{a.name}: largest error {err!r} (group {int(keys[order][bad])}: "
                f"engine {g[bad]!r}, reference {r[bad]!r})"
            )
        worst = max(worst, err)
    return worst


def host(table) -> dict:
    """Device result table → host NumPy arrays (waits for the device)."""
    import jax

    return {k: np.asarray(v) for k, v in jax.device_get(table.columns).items()}


# ---------------------------------------------------------------------------
# running queries


def device_table(cols: dict):
    """Place a dataset on the default device once."""
    import jax
    import jax.numpy as jnp

    from repro.engine.columns import Table

    table = Table({k: jnp.asarray(v) for k, v in cols.items()})
    jax.block_until_ready(table.columns)
    return table


def describe(executor) -> str:
    """The route an executor ran: strategy, kernel route and update."""
    from repro.kernels.ops import _auto_interpret

    ex = executor.plan.execution
    kernel = ex.kernel or "off"
    route = f"route={executor.strategy_label} kernel={kernel} update={ex.update}"
    if kernel != "off":
        route += f" interpret={_auto_interpret(ex.interpret)}"
    return route


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def stream(plan, table):
    """Stream ``table`` through ``plan`` in ``CHUNK_ROWS`` chunks; returns
    the stream's handle and its result on the host."""
    from repro.engine.plans import Scan

    handle = plan.stream(Scan(table, chunk_rows=CHUNK_ROWS))
    return handle, host(handle.result())


def run_plan(qid: str, plan, table, ref, aggs, extra=lambda handle, got: "") -> None:
    """Run ``plan`` over ``table`` twice (cold, then warm), check both
    results, print the query's line."""
    (handle, got), cold = timed(lambda: stream(plan, table))
    compare(got, ref, aggs)
    (handle, got), warm = timed(lambda: stream(plan, table))
    err = compare(got, ref, aggs)
    n = int(got["__num_groups__"][0])
    print(
        f"{qid} {describe(handle.executor)} rows={table.num_rows} groups={n} "
        f"max_err={err!r} cold_s={cold!r} warm_s={warm!r}{extra(handle, got)}",
        flush=True,
    )


def run_server(qid: str, plan, tables, refs, aggs) -> None:
    """One ``AggregationServer`` answering one query per tenant; every
    handle's status and result is checked."""
    from repro.engine.plans import Scan
    from repro.serve.query_server import AggregationServer

    def once():
        server = AggregationServer(slots=len(tables))
        handles = [
            server.submit(plan, Scan(t, chunk_rows=CHUNK_ROWS), tenant=f"t{i}")
            for i, t in enumerate(tables)
        ]
        server.run_until_idle()
        for h in handles:
            if h.status != "done":
                raise RuntimeError(f"{qid} tenant {h.tenant}: {h.status}: {h.error!r}")
        return handles, [host(h.result()) for h in handles]

    (handles, gots), cold = timed(once)
    for got, ref in zip(gots, refs):
        compare(got, ref, aggs)
    (handles, gots), warm = timed(once)
    err = max(compare(got, ref, aggs) for got, ref in zip(gots, refs))
    groups = [int(g["__num_groups__"][0]) for g in gots]
    routes = sorted({describe(h.executor) for h in handles})
    print(
        f"{qid} tenants={len(tables)} {' | '.join(routes)} "
        f"rows={sum(t.num_rows for t in tables)} groups={groups} "
        f"max_err={err!r} cold_s={cold!r} warm_s={warm!r}",
        flush=True,
    )


def one_chip(rows: int, seed: int) -> None:
    from repro.engine.plan_api import GroupByPlan

    q1_aggs = (COUNT, SUM_QTY, AggSpec("mean", "price"), AggSpec("max", "price"))
    q1 = GroupByPlan(keys=("key",), aggs=q1_aggs)

    cols = make_columns(rows, "low", "uniform", seed)
    table = device_table(cols)
    ref = reference(cols, q1_aggs)
    run_plan("Q1", q1, table, ref, q1_aggs)
    q5_aggs = (COUNT, SUM_QTY)
    run_plan(
        "Q5", GroupByPlan(keys=("key",), aggs=q5_aggs, max_groups=64,
                          saturation="grow"),
        table, ref, q5_aggs,
        extra=lambda h, got: f" final_bound={got['key'].shape[0]}",
    )
    del table

    aggs = (COUNT, AggSpec("max", "price"))
    cols = make_columns(rows, "high", "zipf", seed)
    run_plan("Q2", GroupByPlan(keys=("key",), aggs=aggs), device_table(cols),
             reference(cols, aggs), aggs)

    cols = make_columns(rows, "high", "uniform", seed)
    table = device_table(cols)
    ref = reference(cols, (COUNT, SUM_QTY))
    # the partitioned strategy carries one aggregate per plan
    for name, agg in (("Q3.count", COUNT), ("Q3.sum", SUM_QTY)):
        run_plan(name, GroupByPlan(keys=("key",), aggs=(agg,),
                                   strategy="partitioned"),
                 table, ref, (agg,))
    run_plan(
        "Q6", GroupByPlan(keys=("key",), aggs=(COUNT, SUM_QTY),
                          max_groups=1 << 16, saturation="spill"),
        table, ref, (COUNT, SUM_QTY),
        extra=lambda h, got: f" spilled_rows={h.stats().get('spilled_rows')}",
    )
    del table

    aggs = (COUNT, AggSpec("min", "price"))
    cols = make_columns(rows, "high", "heavy", seed)
    run_plan("Q4", GroupByPlan(keys=("key",), aggs=aggs), device_table(cols),
             reference(cols, aggs), aggs)
    del cols

    tenant_cols = [
        make_columns(rows // 4, "low", "uniform", seed + 1 + i) for i in range(4)
    ]
    run_server("Q7", q1, [device_table(c) for c in tenant_cols],
               [reference(c, q1_aggs) for c in tenant_cols], q1_aggs)


def four_chips(rows: int, seed: int) -> None:
    """The sharded strategy on a mesh of all four chips, both merges, each
    also grown from an undersized bound; compared with the NumPy reference
    and with a one-chip ``concurrent`` run of the same query on device 0."""
    import jax

    from repro.engine.plan_api import ExecutionPolicy, GroupByPlan

    aggs = (COUNT, SUM_QTY, AggSpec("max", "price"))
    cols = make_columns(rows, "high", "uniform", seed)
    table = device_table(cols)
    ref = reference(cols, aggs)
    bound = 1 << 22  # above the N/10 groups of up to 40M rows

    base = GroupByPlan(keys=("key",), aggs=aggs, strategy="concurrent",
                       max_groups=bound)
    (_, got_one), secs = timed(lambda: stream(base, table))
    compare(got_one, ref, aggs)
    ref_one = reference_of(got_one, aggs)
    print(f"S0 one-chip concurrent on {jax.devices()[0]} "
          f"groups={int(got_one['__num_groups__'][0])} cold_s={secs!r}", flush=True)

    # jax.make_mesh: the Explicit-axis mesh users get by default
    mesh = jax.make_mesh((len(jax.devices()),), ("data",))
    for merge in ("dense_psum", "all_to_all"):
        for label, max_groups, saturation in (
            ("bound", bound, "raise"), ("grown", 1 << 10, "grow"),
        ):
            plan = GroupByPlan(
                keys=("key",), aggs=aggs, strategy="sharded",
                max_groups=max_groups, saturation=saturation,
                execution=ExecutionPolicy(mesh=mesh, shard_merge=merge),
            )
            (handle, got), cold = timed(lambda: stream(plan, table))
            err = compare(got, ref, aggs)
            compare(got, ref_one, aggs)
            local = handle.executor.local_group_counts()
            if min(local) == 0:
                raise Mismatch(f"a device holds no groups: {local}")
            print(
                f"S {merge} {label} route={handle.executor.strategy_label} "
                f"devices={len(local)} local_groups={local} "
                f"groups={int(got['__num_groups__'][0])} max_err={err!r} "
                f"cold_s={cold!r}", flush=True,
            )


def reference_of(got: dict, aggs) -> dict:
    """An engine result in :func:`reference`'s layout (sorted keys), so two
    engine runs can be held to each other by :func:`compare`."""
    n = int(got["__num_groups__"][0])
    order = np.argsort(got["key"][:n])
    out = {"key": got["key"][:n][order]}
    for a in aggs:
        out[a.name] = np.asarray(got[a.name][:n], np.float64)[order]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rows", type=int, default=6_000_000)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded path across four chips")
    args = ap.parse_args(argv)

    import jax

    from repro.compile_cache import enable_compile_cache

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {dev.platform!r}); "
              "nothing was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"found {len(devices)}", file=sys.stderr)
        return 2
    cache = enable_compile_cache()
    print(f"platform={dev.platform} device_kind={dev.device_kind} "
          f"device_count={len(devices)} jax={jax.__version__} "
          f"rows={args.rows} seed={args.seed} compile_cache={cache}", flush=True)
    if args.chips == 4:
        four_chips(args.rows, args.seed)
    else:
        one_chip(args.rows, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
