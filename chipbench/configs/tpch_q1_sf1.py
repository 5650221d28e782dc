"""TPC-H Q1 (Pricing Summary Report) over SF1 ``lineitem``.

The query is posed as a user poses it: a default ``GroupByPlan`` (strategy
``auto``, no bound, no execution knobs) over ``Scan`` of the columns it
reads, at the scan's own chunk size.  The engine has no projection, filter
or sort operator and reports keys by their hash, so the timed path also
runs :func:`prepare` (the two price expressions and the ship-date filter as
the ``__mask__`` selection vector, on the device, once per query) and
:func:`answer` (the key values and the ORDER BY); a deployment pays for
both.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import keys as K
from chipbench import reference as R
from chipbench.data import tpch_lineitem as L
from repro.engine.columns import Table
from repro.engine.groupby import AggSpec
from repro.engine.plan_api import GroupByPlan

#: the columns the query reads
READS = ("l_returnflag", "l_linestatus", "l_quantity", "l_extendedprice",
         "l_discount", "l_tax", "l_shipdate")
KEYS = ("l_returnflag", "l_linestatus")
SUMS = ("sum_qty", "sum_base_price", "sum_disc_price", "sum_charge")
MEANS = ("avg_qty", "avg_price", "avg_disc")

#: each answer column and the aggregate behind it
AGGS = {
    "sum_qty": AggSpec("sum", "l_quantity"),
    "sum_base_price": AggSpec("sum", "l_extendedprice"),
    "sum_disc_price": AggSpec("sum", "disc_price"),
    "sum_charge": AggSpec("sum", "charge"),
    "avg_qty": AggSpec("mean", "l_quantity"),
    "avg_price": AggSpec("mean", "l_extendedprice"),
    "avg_disc": AggSpec("mean", "l_discount"),
    "count_order": AggSpec("count"),
}
PLAN = GroupByPlan(keys=KEYS, aggs=tuple(AGGS.values()))


def cutoff(cfg) -> int:
    """``DATE '1998-12-01' - INTERVAL DELTA DAY`` in days."""
    return L.day(1998, 12, 1) - int(cfg["delta_days"])


def make_data(cfg, seed: int) -> dict:
    return L.generate(seed, orders=cfg["orders"], lineitems=cfg["lineitems"],
                      parts=cfg["parts"], suppliers=cfg["suppliers"])


def prepare(cfg, data: dict) -> Table:
    return Table(_project(data, cutoff(cfg)))


@jax.jit
def _project(c, last_day):
    disc_price = c["l_extendedprice"] * (1 - c["l_discount"])
    return {
        "l_returnflag": c["l_returnflag"],
        "l_linestatus": c["l_linestatus"],
        "l_quantity": c["l_quantity"],
        "l_extendedprice": c["l_extendedprice"],
        "l_discount": c["l_discount"],
        "disc_price": disc_price,
        "charge": disc_price * (1 + c["l_tax"]),
        "__mask__": c["l_shipdate"] <= last_day,
    }


@functools.lru_cache(maxsize=1)
def _flags() -> K.KeyDictionary:
    """Every pair of the key columns' values in clause 4.2.3: R, A or N,
    and O or F."""
    return K.KeyDictionary(*(np.frombuffer(v, np.uint8)
                             for v in (L.RETURNFLAGS, L.LINESTATUSES)))


def answer(cfg, table: Table) -> dict:
    """Q1's answer from the engine's result table, on the host: each
    group's key values and its aggregates, in ORDER BY l_returnflag,
    l_linestatus."""
    d = _flags()
    c = table.columns
    out = jax.device_get(_ordered(d.hashes, d.values, c["key"], c["__num_groups__"],
                                  {name: c[a.name] for name, a in AGGS.items()}))
    n = int(out.pop("n"))
    return {name: v[:n] for name, v in out.items()}


@jax.jit
def _ordered(hashes, values, key, num_groups, aggs):
    n = num_groups[0]
    (flag, status), found = K.decode(hashes, values, key)
    live = jnp.arange(key.shape[0]) < n
    flag, status = (jnp.where(found, v, 0) for v in (flag, status))
    order = jnp.argsort(jnp.where(live, flag.astype(jnp.int32) * 256 + status,
                                  1 << 16), stable=True)
    out = {name: v[order] for name, v in aggs.items()}
    out.update(l_returnflag=flag[order], l_linestatus=status[order], n=n)
    return out


def _groups(cfg, h: dict):
    keep = h["l_shipdate"] <= cutoff(cfg)
    cols = {k: v[keep] for k, v in h.items()}
    uniq, inv = R.group_index(*(cols[k] for k in KEYS))
    ext = cols["l_extendedprice"].astype(np.float64)
    cols["disc_price"] = ext * (1 - cols["l_discount"].astype(np.float64))
    cols["charge"] = cols["disc_price"] * (1 + cols["l_tax"].astype(np.float64))
    return dict(zip(KEYS, uniq)), inv, cols


def _report(keys, count, sums) -> dict:
    out = dict(keys, count_order=count)
    for name in SUMS:
        out[name] = sums[AGGS[name].column]
    for name in MEANS:
        out[name] = sums[AGGS[name].column] / count
    return out


_SUMMED = ("l_quantity", "l_extendedprice", "disc_price", "charge", "l_discount")


def reference(cfg, h: dict) -> dict:
    """Q1 in float64 NumPy over the host copy ``h`` of the columns it
    reads: the answer's rows in ORDER BY order."""
    keys, inv, cols = _groups(cfg, h)
    g = keys[KEYS[0]].size
    count = np.bincount(inv, minlength=g).astype(np.float64)
    return _report(keys, count, {c: R.group_sum(inv, g, cols[c]) for c in _SUMMED})


def control(cfg, h: dict) -> dict:
    """:func:`reference` computed in bfloat16 (values, expressions and
    accumulators), the precision below the engine's float32."""
    keys, inv, cols = _groups(cfg, h)
    g = keys[KEYS[0]].size
    bf = {c: np.asarray(jnp.asarray(cols[c], jnp.bfloat16), np.float32)
          for c in _SUMMED}
    sums = R.bf16_group_sums(inv, g, {**bf, "*": np.ones(inv.size, np.float32)})
    count = sums.pop("*")
    return _report(keys, count, sums)


def compare(got: dict, ref: dict) -> dict:
    """The numbers compared with the limits: rows wrong (a group missing,
    extra, or out of ORDER BY order), the largest COUNT error, and the
    largest relative error of any SUM or AVG."""
    wrong, pos = R.rows_in_order(got, ref, KEYS)
    return {
        "rows_wrong": wrong,
        "count_err": R.max_abs_err(got, ref, ("count_order",), pos, pos),
        "rel_err": R.max_rel_err(got, ref, SUMS + MEANS, pos, pos),
    }


def bytes_per_query(cfg) -> int:
    """Bytes Q1 must move: every row of the seven columns it reads, plus
    its answer of 4 rows (the two CHAR(1) keys and 8 float32 aggregates)."""
    return cfg["lineitems"] * L.row_bytes(READS) + 4 * (2 + 4 * len(AGGS))
