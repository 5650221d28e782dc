"""The one traffic generator: closed-loop clients, read from a traffic
file (``chipbench/traffic/<mix>.json``).

- ``front_door: collect`` with ``clients: 1``: one client running the
  query back to back through ``GroupByPlan.collect``.
- ``front_door: server``: ``clients`` query streams on one
  ``AggregationServer(slots=clients)`` driven by ``step()``; each stream
  submits its next query when its last one has returned.

A client starts a query only while the window is open; a query that has
started runs to its end.  A query's latency runs from its submission to
its answer on the host: the configuration's ``answer`` of the engine's
result table.
"""
from __future__ import annotations

import time
from dataclasses import dataclass

from jax.profiler import TraceAnnotation

from repro.engine.plans import Scan


@dataclass
class Query:
    client: int
    start: float                # perf_counter at submission
    end: float | None = None    # perf_counter with the result on the host
    result: dict | None = None  # host arrays of the answer
    error: str | None = None


def _collect(config, cfg, data) -> dict:
    return config.answer(cfg, config.PLAN.collect(Scan(config.prepare(cfg, data))))


def run(traffic: dict, config, cfg, data, seconds: float,
        clients: int | None = None) -> list:
    """Drive the window: queries that start within ``seconds`` of now, from
    ``clients`` of the mix's clients (all of them when None)."""
    door = traffic["front_door"]
    if door == "collect":
        if traffic["clients"] != 1:
            raise ValueError("the collect front door takes one client")
        return _run_collect(config, cfg, data, seconds)
    if door == "server":
        return _run_server(traffic["clients"], clients or traffic["clients"],
                           config, cfg, data, seconds)
    raise ValueError(f"unknown front door {door!r}")


def warm(traffic: dict, config, cfg, data) -> None:
    """One query through the cell's own front door."""
    for q in run(traffic, config, cfg, data, 0.0, clients=1):
        if q.error:
            raise RuntimeError(f"warm-up query failed: {q.error}")


def _run_collect(config, cfg, data, seconds) -> list:
    done = []
    t_end = time.perf_counter() + seconds
    while True:
        q = Query(0, time.perf_counter())
        with TraceAnnotation("chipbench.query"):
            try:
                q.result = _collect(config, cfg, data)
            except Exception as err:  # the run reports it under `failed`
                q.error = f"{type(err).__name__}: {err}"
        q.end = time.perf_counter()
        done.append(q)
        if q.error or q.end >= t_end:
            return done


def _run_server(streams, clients, config, cfg, data, seconds) -> list:
    from repro.serve.query_server import AggregationServer

    server = AggregationServer(slots=streams)
    live, done = {}, []
    t_end = time.perf_counter() + seconds

    def submit(c):
        q = Query(c, time.perf_counter())
        with TraceAnnotation("chipbench.submit"):
            source = Scan(config.prepare(cfg, data))
            live[c] = (server.submit(config.PLAN, source, tenant=f"stream{c}"), q)

    for c in range(clients):
        submit(c)
    while live:
        with TraceAnnotation("chipbench.server_step"):
            server.step()
        for c, (h, q) in list(live.items()):
            if not h.done:
                continue
            try:
                q.result = config.answer(cfg, h.result())
            except Exception as err:  # the run reports it under `failed`
                q.error = f"{type(err).__name__}: {err}"
            q.end = time.perf_counter()
            done.append(q)
            del live[c]
            if q.error is None and q.end < t_end:
                submit(c)
    return done
