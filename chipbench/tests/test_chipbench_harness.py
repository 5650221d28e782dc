"""The harness: ``BENCHMARK.json`` against its contract, every name it
gives found as a file, ``run.py`` refusing to run without a TPU, and the
traced run leaving the route and the compiled scan as they are."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from chipbench import harness, peaks

ROOT = harness.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = harness.load_benchmark()


def check_chips_rule(bench):
    """A cell takes 1 or 4 chips, as many as its configuration is laid out
    on (its file's ``chips``, 1 where it states none), and at most half of
    the cells, rounded down, or one, take 4."""
    laid_out = {}
    for c in bench["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            laid_out[c["name"]] = json.load(f).get("chips", 1)
    for w in bench["workloads"]:
        assert w["chips"] in (1, 4) and w["chips"] == laid_out[w["config"]], w
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 2)


def test_benchmark_json_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["chipbench"] and 1 <= BENCH["run_seconds"] <= 51
    configs = {c["name"] for c in BENCH["configs"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("chipbench/")
        assert all(NAME.match(k) for k in c["reduced"])
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        assert all(k in cfg for k in c["reduced"])
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["config"] in configs
        assert 1 <= len(w["why"]) <= 200
    check_chips_rule(BENCH)
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["setup_s"]["bound"] == 0.25
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and "bound" not in m
        assert set(m["workloads"]) <= set(e2e[m["moves"]].get("workloads", cells))
    for w in cells:  # setup_s, another end-to-end metric, a per-layer one
        assert len(harness.metrics_for(BENCH, w, False)) >= 2
        assert harness.metrics_for(BENCH, w, True)


ROWS4 = {"name": "tpch_q18agg_rows4", "source": "s", "reduced": ["scale_factor"],
         "file": "chipbench/tests/configs/tpch_q18agg_rows4.json", "why": "w"}


def _cell(name, config, chips):
    return {"name": name, "config": config, "traffic": "solo", "chips": chips,
            "why": "w"}


FOUR = ("tpch_q18agg_rows4", 4)


@pytest.mark.parametrize("cells, ok", [
    pytest.param([FOUR], True, id="one-of-four"),
    pytest.param([FOUR, FOUR], True, id="two-of-five"),
    pytest.param([FOUR, FOUR, FOUR], True, id="three-of-six"),
    pytest.param([FOUR, FOUR, FOUR, FOUR], False, id="four-of-seven"),
    pytest.param([("tpch_q18agg_sf1", 4)], False, id="config-on-one-chip"),
    pytest.param([("tpch_q18agg_rows4", 1)], False, id="config-on-four-chips"),
    pytest.param([("tpch_q18agg_rows4", 2)], False, id="two-chips"),
])
def test_chips_rule(cells, ok):
    """The rule on ``chips``, on copies of ``BENCHMARK.json`` built in
    memory: each extra cell joins the three one-chip cells."""
    extra = [_cell(f"extra{i}", *c) for i, c in enumerate(cells)]
    bench = dict(BENCH, configs=BENCH["configs"] + [ROWS4],
                 workloads=BENCH["workloads"] + extra)
    if ok:
        check_chips_rule(bench)
    else:
        with pytest.raises(AssertionError):
            check_chips_rule(bench)


def test_chips_rule_among_three_cells():
    """A second four-chip cell among three is one too many."""
    cells = [_cell("a", *FOUR), _cell("b", *FOUR), BENCH["workloads"][0]]
    bench = dict(BENCH, configs=BENCH["configs"] + [ROWS4], workloads=cells)
    with pytest.raises(AssertionError):
        check_chips_rule(bench)
    check_chips_rule(dict(bench, workloads=cells[1:]))


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_cell_files_found_by_name(workload):
    w = harness.cell(BENCH, workload)
    cfg, mod = harness.load_config(BENCH, w["config"])
    for attr in ("READS", "PLAN", "make_data", "prepare", "answer", "reference",
                 "control", "compare", "bytes_per_query"):
        assert hasattr(mod, attr), attr
    assert harness.load_traffic(w["traffic"])["front_door"] in ("collect", "server")
    for trace in (False, True):
        for m in harness.metrics_for(BENCH, workload, trace):
            assert callable(harness.load_metric(m["name"]).read)


def test_new_config_found_by_name(tmp_path):
    """A configuration added as two new files and one entry is found."""
    (tmp_path / "extra_cfg.json").write_text(json.dumps({"name": "extra_cfg", "orders": 5}))
    (tmp_path / "extra_cfg.py").write_text("PLAN = 'the extra query'\n")
    bench = dict(BENCH, configs=BENCH["configs"] + [
        {"name": "extra_cfg", "source": "s", "file": str(tmp_path / "extra_cfg.json"),
         "reduced": [], "why": "w"}])
    cfg, mod = harness.load_config(bench, "extra_cfg")
    assert cfg["orders"] == 5 and mod.PLAN == "the extra query"


def test_unknown_device_kind_raises():
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(ValueError, match="no published peaks"):
        peaks.peaks("cpu")


def _run_py(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "q1.sf1.solo",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def test_run_py_refuses_without_tpu():
    p = _run_py(ROOT)
    assert p.returncode == 2, p.stderr
    assert "no TPU" in p.stderr and p.stdout.strip() == ""


# run.py as the command runs it, with the look for a chip stood in for
_AS_IF_CHIP = """
import runpy, sys, jax
sys.path.insert(0, ".")
from chipbench import harness
harness.check_chips = lambda chips: jax.devices()
sys.argv = ["chipbench/run.py", "--workload", "q1.sf1.solo", "--seed", "1",
            "--seconds", "1", "--trace", "0"]
runpy.run_path("chipbench/run.py", run_name="__main__")
"""


def test_run_py_fails_with_benchmark_files_alone(tmp_path):
    """Past the look for a chip, a directory with only ``BENCHMARK.json``
    and the benchmark's files has no program to run: non-zero, no result."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run([sys.executable, "-c", _AS_IF_CHIP], cwd=tmp_path,
                       env=dict(env, JAX_PLATFORMS="cpu"), capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "No module named 'repro'" in p.stderr, p.stderr[-2000:]


@pytest.mark.parametrize("workload", ["q1.sf1.solo", "q18agg.sf1.solo"])
def test_trace_keeps_route_and_metrics_off(workload, monkeypatch, cpu_run):
    """The traced run resolves the same plan as the untraced one, and never
    turns on ``obs.metrics`` (which recompiles the scan with its event
    vector and takes plans off batched dispatch)."""
    from repro.engine.plan_api import StreamHandle
    from repro.obs import metrics

    seen = []
    real = StreamHandle.result

    def result(self):
        out = real(self)
        p = self.executor.plan
        seen.append((self.executor.strategy_label, p.execution.update,
                     p.execution.kernel, p.max_groups, metrics.enabled()))
        return out

    monkeypatch.setattr(StreamHandle, "result", result)
    routes = []
    for trace in (False, True):
        seen.clear()
        assert cpu_run(workload, trace=trace)["correct"]
        routes.append(list(seen))
    assert routes[0] == routes[1] and routes[0]
    assert not any(r[-1] for r in routes[1])
