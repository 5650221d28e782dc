"""A run with the timed path broken underneath reads ``correct`` false.

Each test skips the harness's look for a chip, drives the rest of a run on
the CPU at a tiny size, and plants one fault in the program: a consume step
that returns its state unchanged, half of the chunks left out, or an answer
altered where the result table is produced.
"""
import importlib
import itertools

import jax.numpy as jnp
import pytest

CELLS = ("q1.sf1.solo", "q18agg.sf1.solo", "q1.sf1.streams2")


def _state_unchanged(monkeypatch):
    groupby = importlib.import_module("repro.engine.groupby")
    real = groupby._consume_scan

    def scan(table, state, km, vm, start, *a, **kw):
        _, _, halts = real(table, state, km, vm, start, *a, **kw)
        return table, state, jnp.zeros_like(halts)

    monkeypatch.setattr(groupby, "_consume_scan", scan)


def _half_left_out(monkeypatch):
    from repro.engine.groupby import GroupByOperator

    real = GroupByOperator.consume_async
    turn = itertools.count()

    def consume_async(self, chunk):
        return real(self, chunk) if next(turn) % 2 == 0 else None

    monkeypatch.setattr(GroupByOperator, "consume_async", consume_async)


def _answer_altered(monkeypatch):
    groupby = importlib.import_module("repro.engine.groupby")
    real = groupby.build_result_table

    def build(aggs, *a, **kw):
        out = real(aggs, *a, **kw)
        name = aggs[-1].name  # Q1's COUNT(*), Q18's SUM: both exact
        out.columns[name] = out.columns[name].at[0].add(1.0)
        return out

    monkeypatch.setattr(groupby, "build_result_table", build)


FAULTS = {"state_unchanged": _state_unchanged, "half_left_out": _half_left_out,
          "answer_altered": _answer_altered}


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(workload, cpu_run):
    r = cpu_run(workload)
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("workload", CELLS[:2])
def test_fault_reads_incorrect(workload, fault, monkeypatch, cpu_run):
    FAULTS[fault](monkeypatch)
    r = cpu_run(workload)
    assert not r["correct"], r["checks"]
    assert r["failed"] >= 1
