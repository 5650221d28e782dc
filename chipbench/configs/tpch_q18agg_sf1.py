"""TPC-H Q18's inner aggregate over SF1 ``lineitem``:
``SELECT l_orderkey FROM lineitem GROUP BY l_orderkey
HAVING SUM(l_quantity) > 300``.

About 1.5M groups of about 4 rows each, clustered by key.  Posed as a
user poses it: a default ``GroupByPlan`` over ``Scan`` of the columns it
reads.  The engine has no HAVING and reports keys by their hash, so the
timed path also runs :func:`answer`: the key values and the HAVING, on the
device.  The answer keeps every group's sum beside the HAVING rows, so the
comparison covers all 1.5M groups, not only the few that pass.
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
READS = ("l_orderkey", "l_quantity")
SUM = "sum(l_quantity)"
PLAN = GroupByPlan(keys=("l_orderkey",), aggs=(AggSpec("sum", "l_quantity"),))


def make_data(cfg, seed: int) -> dict:
    return L.generate(seed, orders=cfg["orders"], lineitems=cfg["lineitems"],
                      parts=cfg["parts"], suppliers=cfg["suppliers"])


def prepare(cfg, data: dict) -> Table:
    return Table({c: data[c] for c in READS})


@functools.lru_cache(maxsize=1)
def _orderkeys(orders: int) -> K.KeyDictionary:
    """Every ``O_ORDERKEY``, the key column's domain."""
    return K.KeyDictionary(L.orderkey(np.arange(orders, dtype=np.int32)))


def answer(cfg, table: Table) -> dict:
    """The answer from the engine's result table, on the host: every
    group's ``l_orderkey`` and sum, and ``having``, whether the group is a
    row of the HAVING answer."""
    d = _orderkeys(cfg["orders"])
    c = table.columns
    out = jax.device_get(_having(d.hashes, d.values, c["key"], c[SUM],
                                 c["__num_groups__"], np.float32(cfg["quantity"])))
    n = int(out.pop("n"))
    return {name: v[:n] for name, v in out.items()}


@jax.jit
def _having(hashes, values, key, total, num_groups, quantity):
    live = jnp.arange(key.shape[0]) < num_groups[0]
    (orderkey,), found = K.decode_one(hashes, values, key, live)
    return {"l_orderkey": jnp.where(found, orderkey, -1), SUM: total,
            "having": total > quantity, "n": num_groups[0]}


def _report(cfg, keys, sums) -> dict:
    return {"l_orderkey": keys, SUM: sums, "having": sums > cfg["quantity"]}


def reference(cfg, h: dict) -> dict:
    """The aggregate and its HAVING in float64 NumPy over the host copy
    ``h`` of the columns it reads, sorted by key."""
    (keys,), inv = R.group_index(h["l_orderkey"])
    return _report(cfg, keys, R.group_sum(inv, keys.size, h["l_quantity"]))


def control(cfg, h: dict) -> dict:
    """:func:`reference` computed in bfloat16."""
    (keys,), inv = R.group_index(h["l_orderkey"])
    q = np.asarray(jnp.asarray(h["l_quantity"], jnp.bfloat16), np.float32)
    return _report(cfg, keys, R.bf16_group_sums(inv, keys.size, {SUM: q})[SUM])


def compare(got: dict, ref: dict) -> dict:
    """Groups wrong, the largest SUM error, and HAVING rows wrong (a key
    in one answer and not the other, or twice), all exact."""
    wrong, gi, ri = R.align(got["l_orderkey"], ref["l_orderkey"])
    having_wrong, _, _ = R.align(got["l_orderkey"][got["having"]],
                                 ref["l_orderkey"][ref["having"]])
    return {"groups_wrong": wrong,
            "sum_err": R.max_abs_err(got, ref, (SUM,), gi, ri),
            "having_wrong": having_wrong}


def bytes_per_query(cfg) -> int:
    """Bytes the aggregate must move: every row of ``l_orderkey`` and
    ``l_quantity``, plus one group per order (a 4-byte key and a float32
    sum)."""
    return cfg["lineitems"] * L.row_bytes(READS) + cfg["orders"] * (4 + 4)
