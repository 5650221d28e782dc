"""``chip_smoke.py`` on the CPU: it refuses to run without a TPU, and its
NumPy reference comparison catches a planted mismatch (its helpers are
imported, so the check runs in this process)."""
import importlib.util
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_exits_nonzero_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), "--rows", "1000"],
        capture_output=True, text=True, env=env, timeout=300, cwd=REPO,
    )
    assert out.returncode != 0
    assert "no TPU found" in out.stderr
    assert '"ok"' not in out.stdout


def _engine_like(ref, aggs, n_pad=7):
    """A result laid out as the engine returns it: float32 columns in a
    shuffled ticket order, padded past ``__num_groups__``."""
    n = ref["key"].size
    perm = np.random.default_rng(3).permutation(n)
    got = {"key": np.concatenate([ref["key"][perm],
                                  np.full(n_pad, 0xFFFFFFFF, np.uint32)])}
    for a in aggs:
        got[a.name] = np.concatenate([ref[a.name][perm].astype(np.float32),
                                      np.zeros(n_pad, np.float32)])
    got["__num_groups__"] = np.full(n + n_pad, n, np.int32)
    return got


@pytest.mark.parametrize("plant", ["none", "count", "max", "mean", "key"])
def test_reference_comparison_catches_planted_mismatch(smoke, plant):
    aggs = (smoke.COUNT, smoke.SUM_QTY, smoke.AggSpec("mean", "price"),
            smoke.AggSpec("max", "price"))
    cols = smoke.make_columns(20_000, "low", "uniform", seed=5)
    ref = smoke.reference(cols, aggs)
    got = _engine_like(ref, aggs)
    if plant == "none":
        assert smoke.compare(got, ref, aggs) <= smoke.MEAN_RTOL
        return
    if plant == "count":
        got["count(*)"][11] += 1.0          # one group counted once too often
    elif plant == "max":
        got["max(price)"][11] = np.nextafter(got["max(price)"][11], np.inf)
    elif plant == "mean":
        got["mean(price)"][11] *= 1 + 3 * smoke.MEAN_RTOL
    else:
        got["key"][11] ^= np.uint32(1)      # a group under a wrong key
    with pytest.raises(smoke.Mismatch):
        smoke.compare(got, ref, aggs)


def test_smoke_query_matches_reference_on_cpu(smoke, capsys):
    """One smoke query end to end at a small size: the engine through
    ``GroupByPlan.collect`` against the script's NumPy reference."""
    from repro.engine.plan_api import GroupByPlan

    aggs = (smoke.COUNT, smoke.SUM_QTY, smoke.AggSpec("mean", "price"),
            smoke.AggSpec("max", "price"))
    cols = smoke.make_columns(30_000, "high", "uniform", seed=2)
    smoke.run_plan("Q", GroupByPlan(keys=("key",), aggs=aggs),
                   smoke.device_table(cols), smoke.reference(cols, aggs), aggs)
    line = capsys.readouterr().out.strip().splitlines()[-1]
    assert line.startswith("Q route=concurrent") and "groups=" in line
