"""A cell's ``chips`` end to end: the devices it gets, the fullest chip's
peak memory, the roofline over the cell's chips, and a run of a four-device
configuration through ``harness.run`` on four CPU devices."""
import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

from chipbench import harness, peaks

HBM = peaks.PEAKS["TPU v5 lite"]["hbm_bytes_per_s"]


def _roofline(chips, busy_s=2.5, queries=3, bytes_per_query=10**9):
    ctx = SimpleNamespace(
        summary=SimpleNamespace(busy_s=busy_s), chips=chips,
        queries=[SimpleNamespace(error=None)] * queries,
        bytes_per_query=bytes_per_query, peaks={"hbm_bytes_per_s": HBM})
    return harness.load_metric("scan_roofline").read(ctx)


def test_roofline_over_the_cells_chips():
    """One chip: one chip's peak, the formula to the bit.  Four chips
    share each query's bytes, so the share reads a quarter."""
    assert _roofline(1) == 100.0 * (3 * 10**9 / HBM) / 2.5
    assert _roofline(4) == pytest.approx(_roofline(1) / 4, rel=1e-15)


class _Device:
    def __init__(self, stats):
        self.stats = stats

    def memory_stats(self):
        return self.stats


def test_memory_peaks_per_device():
    devices = [_Device({"peak_bytes_in_use": 5}), _Device(None), _Device({}),
               _Device({"peak_bytes_in_use": 9})]
    assert harness.memory_peaks(devices) == [5, None, None, 9]


def test_check_chips_gives_the_cells_devices(monkeypatch):
    import jax

    tpus = [SimpleNamespace(platform="tpu", id=i) for i in range(4)]
    monkeypatch.setattr(jax, "devices", lambda: tpus)
    assert harness.check_chips(1) == tpus[:1]
    assert harness.check_chips(4) == tpus
    with pytest.raises(harness.NoChip, match="asks for 8 chips"):
        harness.check_chips(8)


# One run of a four-chip cell of the test configuration at the tiny size,
# on four CPU devices, with the CPU tests' stand-ins; prints what it saw.
_FOUR_DEVICES = r"""
import dataclasses, json, sys, time
import jax, pytest
from chipbench import harness
from chipbench.tests.conftest import stand_ins

PEAKS = [3 << 20, 7 << 20, 5 << 20, 1 << 20]
mp = pytest.MonkeyPatch()
stand_ins(mp)
mp.setattr(harness, "enable_compile_cache", lambda: "off")


class Device:  # a CPU device whose allocator reports a peak
    def __init__(self, device, peak):
        self._device, self._peak = device, peak

    def __getattr__(self, name):
        return getattr(self._device, name)

    def memory_stats(self):
        return {"peak_bytes_in_use": self._peak}


mp.setattr(harness, "check_chips", lambda chips: [
    Device(d, p) for d, p in zip(jax.devices()[:chips], PEAKS)])

bench = harness.load_benchmark()
bench["configs"].append({
    "name": "tpch_q18agg_rows4", "source": "s", "reduced": ["scale_factor"],
    "file": "chipbench/tests/configs/tpch_q18agg_rows4.json", "why": "w"})
bench["workloads"].append({"name": "q18agg.rows4", "config": "tpch_q18agg_rows4",
                           "traffic": "solo", "chips": 4, "why": "w"})
for m in bench["per_layer"]:
    if "q18agg.sf1.solo" in m["workloads"]:
        m["workloads"].append("q18agg.rows4")
mp.setattr(harness, "load_benchmark", lambda root=harness.ROOT: bench)

seen = {}
tiny_load = harness.load_config


def load_config(b, name):
    cfg, mod = tiny_load(b, name)
    cfg["lineitems"] += -cfg["lineitems"] % cfg["chips"]  # rows divide by 4
    make = mod.make_data

    def make_data(cfg, seed):
        data = make(cfg, seed)
        seen["data_devices"] = sorted({d.id for v in data.values()
                                       for d in v.sharding.device_set})
        seen["rows_per_device"] = sorted({s.data.shape[0] for s in
                                          data["l_orderkey"].addressable_shards})
        return data

    mod.make_data = make_data
    return cfg, mod


mp.setattr(harness, "load_config", load_config)
roofline = harness.load_metric("scan_roofline")


def load_metric(name, real=harness.load_metric):
    if name != "scan_roofline":
        return real(name)

    def read(ctx):
        seen["roofline_1"] = roofline.read(dataclasses.replace(ctx, chips=1))
        seen["chips"] = ctx.chips
        return roofline.read(ctx)

    return type("Reader", (), {"read": staticmethod(read)})


mp.setattr(harness, "load_metric", load_metric)
result = harness.run("q18agg.rows4", 7, 0.0, True, t_start=time.perf_counter())
print(json.dumps({"result": result, "seen": seen, "peaks": PEAKS}))
"""


def test_four_device_cell_through_run():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([harness.ROOT, os.path.join(harness.ROOT, "src")]))
    p = subprocess.run([sys.executable, "-c", _FOUR_DEVICES], cwd=harness.ROOT,
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    r, seen = out["result"], out["seen"]
    assert r["correct"] and r["failed"] == 0, r["checks"]
    assert seen["data_devices"] == [0, 1, 2, 3] and len(seen["rows_per_device"]) == 1
    assert r["device"]["count"] == 4 and seen["chips"] == 4
    assert r["device"]["memory_peak_bytes_per_device"] == out["peaks"]
    assert r["device"]["memory_peak_bytes"] == max(out["peaks"])
    roofline = r["metrics"]["scan_roofline"]["value"]
    assert roofline > 0 and roofline == pytest.approx(seen["roofline_1"] / 4, rel=1e-15)
