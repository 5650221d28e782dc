#!/usr/bin/env python3
"""Read the control at a configuration's own size: its float64 reference
recomputed in bfloat16, compared with the reference by the same numbers
and limits that decide a run's ``correct``.

    python chipbench/control.py --config tpch_q1_sf1 --seeds 1 2 3

Prints one JSON line per seed with each number beside its limit.  The
benchmark's own runs never run this; it sets the upper reading of each
limit (see PERF.md).
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    import jax

    from chipbench import harness

    harness.enable_compile_cache()
    cfg, mod = harness.load_config(harness.load_benchmark(), args.config)
    for seed in args.seeds:
        data = mod.make_data(cfg, seed)
        h = jax.device_get({c: data[c] for c in mod.READS})
        del data
        numbers = mod.compare(mod.control(cfg, h), mod.reference(cfg, h))
        print(json.dumps({
            "config": args.config, "seed": seed,
            "device": jax.devices()[0].device_kind,
            "control": {k: {"value": float(v), "limit": cfg["limits"][k]}
                        for k, v in numbers.items()},
            "fails": any(v > cfg["limits"][k] for k, v in numbers.items()),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
