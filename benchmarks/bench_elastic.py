"""Elastic-stream benchmark — recovery-path acceptance gates.

Two recovery mechanisms, measured against the do-nothing alternative of
replaying the whole stream from scratch:

  * **checkpoint/restore** (any strategy) — ``StreamHandle.save`` wall
    time, committed artifact size, and ``GroupByPlan.restore`` wall time
    (deserialize + fast-forward) at an early and a late chunk boundary;
  * **mid-stream re-mesh** (sharded strategy, 4 simulated devices) — kill
    one device at a chunk boundary and re-bucket the carry onto the three
    survivors, vs restarting the stream from row zero on the survivor
    mesh.

Gates:

  * ``remesh_exact`` / ``restore_exact`` — both recovery paths finish
    bit-identical to the one-shot oracle (integer-valued f32 sums, so
    fold order can't hide a wrong re-bucket);
  * ``recovery_ratio`` — killing a device and re-meshing, THEN finishing
    the stream, must not cost more than 1.5× the full from-scratch replay
    on the survivor mesh.  Elasticity is pointless if recovering is slower
    than starting over.

Emits ``common.emit`` CSV; ``--json PATH`` writes ``BENCH_elastic.json``
(compared against ``benchmarks/baselines/`` by ``check_regression.py``).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.common import (N_ROWS, emit, gate, run_in_devices, time_fn,
                               write_bench_json)
from repro.core import groupby_oracle
from repro.engine import AggSpec, GroupByPlan, SaturationPolicy, Table

CHUNKS = 16
CARD = 512


def _data(n: int, seed: int = 11):
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, CARD, size=n).astype(np.uint32)
    # integer-valued f32: any summation order is exact below 2**24
    vals = rng.integers(0, 100, size=n).astype(np.float32)
    return keys, vals


def _chunked(keys, vals, chunks=CHUNKS):
    step = keys.shape[0] // chunks
    for i in range(0, keys.shape[0], step):
        yield Table({"k": jnp.asarray(keys[i:i + step]),
                     "v": jnp.asarray(vals[i:i + step])})


def _tmap(out):
    n = int(out["__num_groups__"][0])
    return {int(k): float(v)
            for k, v in zip(np.asarray(out["key"])[:n],
                            np.asarray(out["sum(v)"])[:n])}


def _dir_bytes(path: str) -> int:
    total = 0
    for root, _, files in os.walk(path):
        total += sum(os.path.getsize(os.path.join(root, f)) for f in files)
    return total


def _checkpoint_costs(n: int) -> dict:
    keys, vals = _data(n)
    ref = groupby_oracle(jnp.asarray(keys), jnp.asarray(vals),
                         kind="sum", max_groups=CARD)
    ng = int(ref.num_groups)
    oracle = {int(k): float(v) for k, v in
              zip(np.asarray(ref.keys)[:ng], np.asarray(ref.values)[:ng])}
    plan = GroupByPlan(
        keys=("k",), aggs=(AggSpec("sum", "v"), AggSpec("count")),
        strategy="concurrent", max_groups=CARD,
        saturation=SaturationPolicy.GROW, raw_keys=True,
    )
    out = {}
    all_exact = True
    for label, snap_at in (("early", 2), ("late", CHUNKS - 2)):
        h = plan.stream(_chunked(keys, vals))
        h.pump(snap_at)
        with tempfile.TemporaryDirectory() as d:
            # fixed step: each timed save atomically replaces the last
            save_us = time_fn(lambda: h.save(d, step=snap_at),
                              warmup=1, runs=3)
            ckpt_bytes = _dir_bytes(d)

            def restore():
                h2 = plan.restore(d, _chunked(keys, vals))
                return h2

            restore_us = time_fn(lambda: restore().cancel() or 0,
                                 warmup=1, runs=3)
            exact = _tmap(restore().result()) == oracle
        all_exact = all_exact and exact
        out[label] = {"snap_at": snap_at, "save_us": save_us,
                      "restore_us": restore_us, "ckpt_bytes": ckpt_bytes,
                      "exact": exact}
        emit(f"elastic_save_{label}", save_us,
             f"chunk {snap_at}/{CHUNKS}, commit={ckpt_bytes}B")
        emit(f"elastic_restore_{label}", restore_us,
             f"deserialize+fast-forward, exact={'yes' if exact else 'NO'}")
    out["exact"] = all_exact
    return out


_REMESH_CODE = r"""
import json, time
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh
from repro.engine.plan_api import (AggSpec, ExecutionPolicy, GroupByPlan,
                                   SaturationPolicy)
from repro.engine.columns import Table
from repro.engine import elastic as streams
from repro.train import elastic as telastic

N, CHUNKS, CARD, FAIL_AT = %(n)d, %(chunks)d, %(card)d, %(fail_at)d
rng = np.random.default_rng(11)
keys = rng.integers(0, CARD, N).astype(np.uint32)
vals = rng.integers(0, 100, N).astype(np.float32)

class Src:
    def chunks(self):
        step = N // CHUNKS
        for i in range(0, N, step):
            yield Table({"k": jnp.asarray(keys[i:i+step]),
                         "v": jnp.asarray(vals[i:i+step])})

def tmap(out):
    n = int(np.asarray(out["__num_groups__"])[0])
    return {int(a): float(b) for a, b in
            zip(np.asarray(out["key"])[:n], np.asarray(out["sum(v)"])[:n])}

def plan_on(devs):
    return GroupByPlan(
        keys=["k"], aggs=[AggSpec("sum", "v"), AggSpec("count")],
        strategy="sharded", max_groups=CARD, raw_keys=True,
        saturation=SaturationPolicy.GROW,
        execution=ExecutionPolicy(mesh=Mesh(np.asarray(devs), ("data",))))

oracle = tmap(plan_on(jax.devices()).collect(Src()))

# warm both meshes' compiled paths so timings measure recovery, not jit
tmap(plan_on(jax.devices()[:-1]).collect(Src()))

# -- kill-one-device recovery: re-mesh the live carry, finish the stream --
telastic.reset_failures()
h = plan_on(jax.devices()).stream(Src())
h.pump(FAIL_AT)
telastic.mark_failed([jax.devices()[-1].id])
t0 = time.perf_counter()
assert streams.remesh_stream(h)
remesh_us = (time.perf_counter() - t0) * 1e6
t0 = time.perf_counter()
remesh_exact = tmap(h.result()) == oracle
finish_us = (time.perf_counter() - t0) * 1e6
telastic.reset_failures()

# -- the alternative: throw the carry away, replay from row 0 on survivors --
t0 = time.perf_counter()
replay_exact = tmap(plan_on(jax.devices()[:-1]).collect(Src())) == oracle
replay_us = (time.perf_counter() - t0) * 1e6

print(json.dumps({
    "remesh_us": remesh_us, "finish_us": finish_us,
    "recovery_us": remesh_us + finish_us, "replay_us": replay_us,
    "ratio": (remesh_us + finish_us) / max(replay_us, 1e-9),
    "remesh_exact": bool(remesh_exact), "replay_exact": bool(replay_exact),
}))
"""


def run(n: int | None = None, json_path: str | None = None):
    n = n or N_ROWS
    results = {"n_rows": n, "chunks": CHUNKS, "cardinality": CARD}

    results["checkpoint"] = _checkpoint_costs(n)

    mesh = run_in_devices(4, _REMESH_CODE % {
        "n": n, "chunks": CHUNKS, "card": CARD, "fail_at": CHUNKS // 2,
    })
    results["remesh"] = mesh
    emit("elastic_remesh", mesh["remesh_us"],
         f"re-bucket 4→3 devices at chunk {CHUNKS // 2}/{CHUNKS}, "
         f"exact={'yes' if mesh['remesh_exact'] else 'NO'} "
         f"platform={mesh['platform']}")
    emit("elastic_recovery", mesh["recovery_us"],
         f"re-mesh + finish vs {mesh['replay_us']:.0f}us full replay "
         f"(ratio {mesh['ratio']:.2f})")

    restore_exact = results["checkpoint"]["exact"]
    emit("elastic_exact",
         1.0 if (restore_exact and mesh["remesh_exact"]) else 0.0,
         "restore and re-mesh both bit-exact vs oracle"
         if restore_exact and mesh["remesh_exact"] else "MISMATCH")

    results["exact"] = bool(restore_exact and mesh["remesh_exact"])
    results["recovery_ratio"] = mesh["ratio"]
    if json_path:
        write_bench_json(json_path, "elastic", results, gates={
            "remesh_exact": gate(mesh["remesh_exact"], "==", True),
            "restore_exact": gate(restore_exact, "==", True),
            "recovery_ratio": gate(mesh["ratio"], "<=", 1.5),
        })
    return results


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None,
                    help="write BENCH_elastic.json here")
    ap.add_argument("--rows", type=int, default=None)
    args = ap.parse_args()
    print("name,us_per_call,derived", flush=True)
    run(args.rows, json_path=args.json)
