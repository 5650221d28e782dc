"""Dry-run machinery test: one real cell lowers+compiles on the production
mesh in a subprocess (512 forced host devices), and the roofline parser
extracts sane terms. Covers deliverable (e) logic end-to-end."""
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_dryrun_single_cell_production_mesh():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # dryrun.py sets its own
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    code = """
import json
from repro.launch.dryrun import run_cell
res = run_cell("qwen3_0_6b", "train_4k", multi_pod=False, verbose=False,
               with_cost=False)
out = {
  "flops": res["cost_raw_scanned"]["flops"],
  "coll": sum(v for k, v in res["collectives_raw_scanned"].items() if k != "counts"),
  "peak": res["memory"]["peak_bytes"],
  "bottleneck": res["roofline"]["bottleneck"],
}
print(json.dumps(out))
"""
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["flops"] > 1e11          # nontrivial per-device compute
    assert res["coll"] > 1e8            # TP collectives present
    assert 0 < res["peak"] < 16 * 2**30  # fits v5e HBM
    assert res["bottleneck"] in ("compute", "memory", "collective")


def test_collective_parser():
    from repro.launch.roofline import collective_bytes

    hlo = """
  %all-reduce.188 = f32[16,4096,1]{2,1,0} all-reduce(%wrapped_reduce), replica_groups=[128,2]<=[256]
  %all-gather.9 = bf16[16,4096,128]{2,1,0} all-gather(%bitcast), dimensions={2}
  %ag-done = f32[4,4]{1,0} all-gather-done(%x)
  %name.1 = f32[2,2]{1,0} add(%a, %b)
"""
    out = collective_bytes(hlo)
    assert out["all-reduce"] == 16 * 4096 * 1 * 4
    assert out["all-gather"] == 16 * 4096 * 128 * 2
    assert out["all-to-all"] == 0
