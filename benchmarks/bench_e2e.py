"""Paper Fig. 6 + Table 2 — end-to-end concurrent vs partitioned, with a
device-count scaling sweep (threads ⇒ simulated devices, in subprocesses).

Single-device section compares the algorithms' total work (the paper's
1-thread column).  The scaling section runs concurrent_groupby_sharded and
partitioned_groupby_sharded on k ∈ {1,2,4,8} simulated host devices and
reports the Table-2 speedup matrix (concurrent latency / partitioned
latency per workload × k).
"""
from __future__ import annotations

import json

import jax.numpy as jnp

from benchmarks.common import N_ROWS, emit, gen_keys, run_in_devices, time_fn
from repro.core import concurrent_groupby, partitioned_groupby

_SCALING_CODE = """
import json, time
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core.distributed import concurrent_groupby_sharded, partitioned_groupby_sharded
from benchmarks.common import gen_keys

k = len(jax.devices())
mesh = jax.make_mesh((k,), ("data",))
n = {n}
keys = gen_keys(n, "{card}", "{dist}")
vals = np.random.default_rng(0).normal(size=n).astype("float32")
sh = NamedSharding(mesh, P("data"))
kd = jax.device_put(jnp.asarray(keys), sh)
vd = jax.device_put(jnp.asarray(vals), sh)
uniq = {{"low": 1000, "high": n // 10, "unique": n}}["{card}"]

def bench(fn):
    jax.block_until_ready(fn())
    ts = []
    for _ in range(3):
        t0 = time.perf_counter(); jax.block_until_ready(fn()); ts.append(time.perf_counter()-t0)
    return float(np.median(ts) * 1e6)

us_conc = bench(lambda: concurrent_groupby_sharded(mesh, kd, vd, kind="sum", max_groups=uniq))
us_part = bench(lambda: partitioned_groupby_sharded(mesh, kd, vd, kind="sum", max_groups=uniq,
                                                    preagg_capacity=4096)[1])
print(json.dumps({{"k": k, "us_conc": us_conc, "us_part": us_part}}))
"""


def run(n=None, scaling=True):
    n = n or min(N_ROWS, 1 << 19)
    workloads = [
        ("low", "uniform"), ("low", "zipf"), ("low", "heavy"),
        ("high", "uniform"), ("high", "zipf"), ("high", "heavy"),
        ("unique", "uniform"),
    ]
    # -- single-device total-work comparison (paper 1-thread column) -------
    for card, dist in workloads:
        keys = jnp.asarray(gen_keys(n, card, dist))
        uniq = {"low": 1000, "high": n // 10, "unique": n}[card]
        us_c = time_fn(
            lambda k: concurrent_groupby(k, None, kind="count", update="scatter",
                                         max_groups=uniq).values, keys
        )
        us_p = time_fn(
            lambda k: partitioned_groupby(k, None, kind="count", max_groups=uniq,
                                          num_workers=8, preagg_capacity=4096).values,
            keys,
        )
        emit(f"fig6_concurrent_{card}_{dist}", us_c, f"n={n}")
        emit(
            f"fig6_partitioned_{card}_{dist}", us_p,
            f"n={n};speedup_conc={us_p/us_c:.2f}x",
        )
    # -- device scaling (Table 2 matrix) ------------------------------------
    if not scaling:
        return
    for card, dist in [("low", "uniform"), ("high", "uniform"), ("high", "heavy"), ("unique", "uniform")]:
        base = None
        for k in [1, 2, 4, 8]:
            try:
                res = run_in_devices(
                    k, _SCALING_CODE.format(n=min(n, 1 << 18), card=card, dist=dist)
                )
            except Exception as e:  # noqa: BLE001
                emit(f"table2_{card}_{dist}_k{k}", -1.0, f"failed:{e}")
                continue
            if base is None:
                base = res
            emit(
                f"table2_conc_{card}_{dist}_k{k}", res["us_conc"],
                f"speedup_vs1={base['us_conc']/res['us_conc']:.2f};vs_part={res['us_part']/res['us_conc']:.2f};platform={res['platform']}",
            )
            emit(
                f"table2_part_{card}_{dist}_k{k}", res["us_part"],
                f"speedup_vs1={base['us_part']/res['us_part']:.2f};platform={res['platform']}",
            )


if __name__ == "__main__":
    run()
