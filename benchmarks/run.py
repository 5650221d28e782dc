"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  Set BENCH_ROWS to scale the row
count (default 1M); BENCH_QUICK=1 runs a reduced sweep for CI.
"""
from __future__ import annotations

import os
import sys
import traceback

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [REPO, os.path.join(REPO, "src")]

QUICK = os.environ.get("BENCH_QUICK", "0") == "1"


def main() -> None:
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (
        bench_breakdown,
        bench_e2e,
        bench_elastic,
        bench_fused,
        bench_hybrid,
        bench_memory,
        bench_plan,
        bench_resize,
        bench_roofline,
        bench_serve,
        bench_spill,
        bench_stream,
        bench_ticketer,
        bench_ticketing,
        bench_updates,
    )

    n = (1 << 16) if QUICK else None
    print("name,us_per_call,derived", flush=True)
    suites = [
        ("fig3", lambda: bench_ticketer.run(n=(1 << 14) if QUICK else None)),
        ("fig4", lambda: bench_ticketing.run(n=n)),
        ("fig5", lambda: bench_updates.run(n=n)),
        ("fig6+table2", lambda: bench_e2e.run(n=n, scaling=not QUICK)),
        ("fig7", lambda: bench_breakdown.run(n=n)),
        ("fig8", lambda: bench_resize.run(n=n)),
        ("table3", lambda: bench_memory.run(n=n)),
        ("hybrid", lambda: bench_hybrid.run(n=n)),
        ("plan_sweep", lambda: bench_plan.run(n=n)),
        ("streaming", lambda: bench_stream.run(
            n=n, json_path=os.environ.get("BENCH_STREAM_JSON"))),
        ("serving", lambda: bench_serve.run(
            n=n, json_path=os.environ.get("BENCH_SERVE_JSON"))),
        ("spill", lambda: bench_spill.run(
            n=n, json_path=os.environ.get("BENCH_SPILL_JSON"))),
        ("fused", lambda: bench_fused.run(
            n=n, json_path=os.environ.get("BENCH_FUSED_JSON"))),
        ("elastic", lambda: bench_elastic.run(
            n=n, json_path=os.environ.get("BENCH_ELASTIC_JSON"))),
        ("roofline", bench_roofline.run),
    ]
    for name, fn in suites:
        try:
            fn()
        except Exception:  # noqa: BLE001 — one suite failing must not hide others
            err = traceback.format_exc(limit=2).splitlines()[-1].replace(",", ";")
            print(f"{name}_FAILED,-1,{err}", flush=True)


if __name__ == "__main__":
    main()
