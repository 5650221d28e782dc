"""Distributed tests — run in persistent WARMED subprocesses so the main
pytest process keeps exactly 1 device.

One worker interpreter per simulated device count, shared by every test
that needs that count (tier-1 wall-clock: the jax import + XLA client
startup — several seconds per interpreter — is paid once per device count
instead of once per test).  Each request executes in a fresh globals dict,
so tests stay isolated at the Python level while sharing the warm jax
runtime and its compilation cache."""
import atexit
import json
import os
import subprocess
import sys
import tempfile
import threading

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Reads one JSON line {"code": ...} per request, execs it with stdout
# captured, replies with one JSON line {"out": last-printed-line} or
# {"err": traceback}.  Native stderr goes to a log file (see _get_worker).
_DRIVER = r"""
import contextlib, io, json, sys, traceback
for line in sys.stdin:
    req = json.loads(line)
    buf = io.StringIO()
    try:
        with contextlib.redirect_stdout(buf):
            exec(compile(req["code"], "<distributed-test>", "exec"),
                 {"__name__": "__worker__"})
        out = buf.getvalue().strip().splitlines()
        payload = {"out": out[-1] if out else ""}
    except BaseException:
        payload = {"err": traceback.format_exc()[-3000:],
                   "out": buf.getvalue()[-2000:]}
    sys.stdout.write(json.dumps(payload) + "\n")
    sys.stdout.flush()
"""

_WORKERS: dict[int, tuple] = {}


def _get_worker(k: int):
    worker = _WORKERS.get(k)
    if worker is not None and worker[0].poll() is None:
        return worker
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={k}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    errlog = tempfile.NamedTemporaryFile(
        mode="w+", prefix=f"distworker{k}-", suffix=".log", delete=False
    )
    proc = subprocess.Popen(
        [sys.executable, "-c", _DRIVER], stdin=subprocess.PIPE,
        stdout=subprocess.PIPE, stderr=errlog, text=True, env=env,
    )
    _WORKERS[k] = (proc, errlog.name)
    return _WORKERS[k]


@atexit.register
def _shutdown_workers():
    for proc, _ in _WORKERS.values():
        if proc.poll() is None:
            proc.kill()


def run_with_devices(k: int, code: str, timeout: float = 900) -> dict:
    proc, errpath = _get_worker(k)
    proc.stdin.write(json.dumps({"code": code}) + "\n")
    proc.stdin.flush()
    reply: dict = {}

    def _read():
        reply["line"] = proc.stdout.readline()

    reader = threading.Thread(target=_read, daemon=True)
    reader.start()
    reader.join(timeout)
    if not reply.get("line"):
        proc.kill()
        _WORKERS.pop(k, None)
        with open(errpath) as f:
            tail = f.read()[-3000:]
        pytest.fail(f"device-count-{k} worker hung or died; stderr:\n{tail}")
    payload = json.loads(reply["line"])
    assert "err" not in payload, payload.get("err")
    return json.loads(payload["out"])


def test_concurrent_sharded_matches_oracle():
    res = run_with_devices(8, """
import json
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core.distributed import concurrent_groupby_sharded
from repro.core import groupby_oracle
mesh = jax.make_mesh((8,), ("data",))
rng = np.random.default_rng(1)
keys = rng.integers(0, 200, size=8192).astype(np.uint32)
vals = rng.normal(size=8192).astype(np.float32)
sh = NamedSharding(mesh, P("data"))
kd, vd = jax.device_put(jnp.asarray(keys), sh), jax.device_put(jnp.asarray(vals), sh)
ok = True
for kind in ["count", "sum", "min", "max"]:
    ref = groupby_oracle(jnp.asarray(keys), jnp.asarray(vals), kind=kind, max_groups=256)
    got = concurrent_groupby_sharded(mesh, kd, vd, kind=kind, max_groups=256)
    n = int(ref.num_groups)
    rm = dict(zip(np.asarray(ref.keys)[:n].tolist(), np.asarray(ref.values)[:n].tolist()))
    m = int(got.num_groups)
    gm = dict(zip(np.asarray(got.keys)[:m].tolist(), np.asarray(got.values)[:m].tolist()))
    ok &= rm.keys() == gm.keys() and all(abs(rm[k]-gm[k]) < 1e-2 for k in rm)
print(json.dumps({"ok": bool(ok)}))
""")
    assert res["ok"]


def test_partitioned_sharded_all_to_all():
    res = run_with_devices(8, """
import json
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core.distributed import partitioned_groupby_sharded
from repro.core import groupby_oracle
mesh = jax.make_mesh((8,), ("data",))
rng = np.random.default_rng(1)
keys = rng.integers(0, 200, size=8192).astype(np.uint32)
vals = rng.normal(size=8192).astype(np.float32)
sh = NamedSharding(mesh, P("data"))
kd, vd = jax.device_put(jnp.asarray(keys), sh), jax.device_put(jnp.asarray(vals), sh)
keys_p, vals_p, counts_p, ovf = partitioned_groupby_sharded(
    mesh, kd, vd, kind="sum", max_groups=256, preagg_capacity=512)
assert int(jnp.sum(ovf)) == 0
kp = np.asarray(keys_p).reshape(8, -1); vp = np.asarray(vals_p).reshape(8, -1)
cp = np.asarray(counts_p)
got = {}
for d in range(8):
    for k, v in zip(kp[d][:int(cp[d])], vp[d][:int(cp[d])]):
        assert int(k) not in got
        got[int(k)] = float(v)
ref = groupby_oracle(jnp.asarray(keys), jnp.asarray(vals), kind="sum", max_groups=256)
n = int(ref.num_groups)
rm = dict(zip(np.asarray(ref.keys)[:n].tolist(), np.asarray(ref.values)[:n].tolist()))
ok = rm.keys() == got.keys() and all(abs(rm[k]-got[k]) < 1e-2 for k in rm)
print(json.dumps({"ok": bool(ok)}))
""")
    assert res["ok"]


@pytest.mark.slow
def test_manual_dp_train_step_with_compression():
    res = run_with_devices(8, """
import json
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.models import transformer as tf
from repro.optim import adamw
from repro.train.loop import TrainHParams, make_manual_dp_step
mesh = jax.make_mesh((2, 4), ("pod", "data"))
cfg = get_config("qwen3_0_6b", reduced=True)
hp = TrainHParams(ticketed_embedding=False, grad_compression="int8")
params = tf.init_params(jax.random.PRNGKey(0), cfg)
opt = adamw.init(params)
tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab_size)
batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, 1)}
step = make_manual_dp_step(mesh, cfg, hp)
losses = []
for i in range(4):
    params, opt, m = step(params, opt, batch)
    losses.append(float(m["loss"]))
print(json.dumps({"losses": losses, "finite": all(np.isfinite(losses))}))
""")
    assert res["finite"]
    assert res["losses"][-1] < res["losses"][0], res["losses"]


def test_ep_moe_matches_dense_dispatch():
    res = run_with_devices(4, """
import json
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.configs import get_config
from repro.models import moe as moe_lib
cfg = get_config("granite_moe_1b_a400m", reduced=True)  # 8 experts top-2
mesh = jax.make_mesh((4,), ("model",))
p = moe_lib.moe_init(jax.random.PRNGKey(0), cfg)
x = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
dense_out, dense_aux = moe_lib.moe_mlp_dense(p, cfg, x)

e_local = cfg.moe_num_experts // 4
cap = 64  # ample capacity: no drops → must match dense exactly
def run_ep(x, pg, pu, pd, prouter):
    p_loc = {"router": prouter, "w_gate": pg, "w_up": pu, "w_down": pd}
    out, aux = moe_lib.moe_mlp_ep(p_loc, cfg, x, axis="model", num_shards=4,
                                  capacity_per_expert=cap)
    return out, aux
from jax import shard_map
fn = shard_map(run_ep, mesh=mesh,
        in_specs=(P(), P("model"), P("model"), P("model"), P()),
        out_specs=(P(), P()), check_vma=False)
ep_out, ep_aux = fn(x, p["w_gate"], p["w_up"], p["w_down"], p["router"])
rel = float(jnp.max(jnp.abs(ep_out - dense_out))) / (float(jnp.max(jnp.abs(dense_out))) + 1e-9)
print(json.dumps({"rel": rel}))
""")
    assert res["rel"] < 0.05, res


def test_multipod_mesh_tiny():
    """3-axis (pod,data,model) mesh end-to-end on 8 devices."""
    res = run_with_devices(8, """
import json
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config
from repro.models import transformer as tf
from repro.optim import adamw
from repro.train.loop import TrainHParams, make_train_step
from repro.parallel.sharding import param_specs
from repro.launch.mesh import make_auto_mesh
mesh = make_auto_mesh((2, 2, 2), ("pod", "data", "model"))
cfg = get_config("qwen3_0_6b", reduced=True)
params = tf.init_params(jax.random.PRNGKey(0), cfg)
opt = adamw.init(params)
psh = jax.tree.map(lambda s: NamedSharding(mesh, s), param_specs(params))
osh = adamw.AdamWState(step=NamedSharding(mesh, P()),
                       m=jax.tree.map(lambda s: NamedSharding(mesh, s), param_specs(opt.m)),
                       v=jax.tree.map(lambda s: NamedSharding(mesh, s), param_specs(opt.v)))
params = jax.device_put(params, psh)
opt = jax.device_put(opt, osh)
tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab_size)
bsh = {"tokens": NamedSharding(mesh, P(("pod","data"), None)),
       "targets": NamedSharding(mesh, P(("pod","data"), None))}
batch = jax.device_put({"tokens": tokens, "targets": jnp.roll(tokens, -1, 1)}, bsh)
step = jax.jit(make_train_step(cfg, TrainHParams(ticketed_embedding=False)),
               in_shardings=(psh, osh, bsh), donate_argnums=(0,1))
params, opt, m = step(params, opt, batch)
params, opt, m = step(params, opt, batch)
print(json.dumps({"loss": float(m["loss"]), "gnorm": float(m["grad_norm"])}))
""")
    assert res["loss"] > 0 and res["gnorm"] > 0
