import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)
# ^ MUST precede every other import: jax locks the device count at first
#   backend initialization. 512 host devices cover both the 16×16 single-pod
#   mesh (256 used) and the 2×16×16 multi-pod mesh.

"""Multi-pod dry-run: ``lower().compile()`` every (arch × shape × mesh) cell.
(No ``from __future__ import`` here — the XLA_FLAGS lines above must stay
the first statements in the file.)

For each cell the step function the cell's mode dictates is lowered with
ShapeDtypeStruct stand-ins (zero allocation), compiled for the production
mesh, and the artifacts recorded:

  * ``compiled.memory_analysis()``  — proves the cell fits per-device HBM,
  * ``compiled.cost_analysis()``    — FLOPs / bytes for §Roofline,
  * post-SPMD HLO collective scan   — collective bytes for §Roofline.

Usage:
  python -m repro.launch.dryrun --arch gemma2_2b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--out DIR]
"""
import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import ARCH_IDS, applicable_shapes, get_config
from repro.launch import specs as sp
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import (
    collective_bytes,
    derive_terms,
    model_flops_estimate,
)
from repro.models import transformer as tf
from repro.models.config import SHAPES, ModelConfig, ShapeCell
from repro.optim import adamw
from repro.parallel.sharding import param_specs
from repro.train.loop import TrainHParams, make_train_step


def _shardings(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree)


def _ep_info(mesh, cfg: ModelConfig, cell: ShapeCell, variant: str | None = None):
    """Expert-parallel dispatch parameters for MoE cells: tokens-per-device
    and per-(sender, expert) capacity with 1.25 slack (paper-style
    partitioned dispatch buckets).  variant "moe_ts" slices tokens over the
    model axis before dispatch (§Perf iteration 2)."""
    from repro.parallel.sharding import dp_axes

    if not cfg.moe_num_experts:
        return None, "dense"
    dp = dp_axes(mesh)
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    toks = cell.global_batch * (cell.seq_len if cell.mode != "decode" else 1)
    t_local = max(toks // dp_size, 1)
    token_slice = variant in ("moe_ts", "moe_ts2", "moe_ts3")
    if token_slice:
        t_local = max(t_local // mesh.shape["model"], 1)
    cap_factor = 1.0 if variant in ("moe_ts2", "moe_ts3") else 1.25
    cap = t_local * cfg.moe_top_k * cap_factor / cfg.moe_experts_padded
    cap = max(8, int((cap + 7) // 8 * 8))
    return {
        "mesh": mesh, "dp": dp, "capacity_per_expert": cap,
        "token_slice": token_slice,
        "quantize_dispatch": variant in ("moe_ts2", "moe_ts3"),
    }, "ep"


def _lower_twobuf_decode(mesh, cfg: ModelConfig, cell: ShapeCell, psh, params_sds,
                         quantized: bool = False):
    """§Perf iteration 1: decode against a frozen sequence-sharded prefix +
    replicated tail (flash-decoding two-buffer layout).  quantized=True
    stores the prefix in int8 (halved cache-read bytes)."""
    from repro.parallel.sharding import dp_axes

    dp = dp_axes(mesh)
    prefix_sds, tail_sds = jax.eval_shape(
        lambda: tf.init_twobuf_caches(cfg, cell.global_batch, cell.seq_len, 512,
                                      jnp.dtype(cfg.dtype))
    )
    if quantized:
        prefix_sds = prefix_sds._replace(
            k=jax.ShapeDtypeStruct(prefix_sds.k.shape, jnp.int8),
            v=jax.ShapeDtypeStruct(prefix_sds.v.shape, jnp.int8),
        )

    def cspec(seq_axis):
        def s(path, leaf):
            import numpy as np
            nd = np.ndim(leaf)
            name = "/".join(str(getattr(k, "name", getattr(k, "key", k))) for k in path)
            if name.split("/")[-1] in ("k", "v"):
                return P(None, dp, seq_axis, None, None)
            return P()
        return jax.tree_util.tree_map_with_path(s, prefix_sds)

    prefix_spec = cspec("model")
    tail_spec = jax.tree_util.tree_map_with_path(
        lambda path, leaf: (
            P(None, dp, None, None, None)
            if str(getattr(path[-1], "name", "")) in ("k", "v") else P()
        ),
        tail_sds,
    )
    psh_pre = _shardings(mesh, prefix_spec)
    psh_tail = _shardings(mesh, tail_spec)
    tok_spec = P(dp, None)
    tok_sds = jax.ShapeDtypeStruct((cell.global_batch, 1), jnp.int32)

    def serve_step(params, tokens, prefix, tail):
        logits, new_tail = tf.decode_step_twobuf(params, cfg, tokens, prefix, tail)
        nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        return nxt[:, None], new_tail

    fn = jax.jit(
        serve_step,
        in_shardings=(psh, NamedSharding(mesh, tok_spec), psh_pre, psh_tail),
        # new-tail out sharding left to XLA: forcing replication at the
        # scan boundary costs a 1.7 GB stacked-tail all-gather (§Perf)
        out_shardings=(NamedSharding(mesh, tok_spec), None),
    )
    return fn.lower(params_sds, tok_sds, prefix_sds, tail_sds)


def lower_cell(mesh, cfg: ModelConfig, cell: ShapeCell, variant: str | None = None):
    """Returns the lowered step for one cell (+ optional §Perf variant)."""
    import dataclasses
    if variant in ("remat_dots", "moe_ts3"):
        cfg = dataclasses.replace(cfg, remat_policy="dots")
    if variant == "remat_bf16logits":
        cfg = dataclasses.replace(cfg, remat_policy="dots", logits_dtype="bfloat16")
    params_sds = sp.abstract_params(cfg)
    if variant == "twobuf_q8w":
        from repro.models.layers import quantize_dense_params
        params_sds = quantize_dense_params(params_sds)
    pspecs = param_specs(params_sds)
    psh = _shardings(mesh, pspecs)
    ep_info, moe_impl = _ep_info(mesh, cfg, cell, variant)

    if variant in ("twobuf", "twobuf_q8", "twobuf_q8w"):
        assert cell.mode == "decode"
        return _lower_twobuf_decode(mesh, cfg, cell, psh, params_sds,
                                    quantized=variant in ("twobuf_q8", "twobuf_q8w"))

    if cell.mode == "train":
        hp = TrainHParams(ticketed_embedding=(variant == "ticketed"))
        step = make_train_step(cfg, hp, moe_impl=moe_impl, ep_info=ep_info)
        opt_sds = sp.abstract_opt(params_sds)
        osh = adamw.AdamWState(
            step=NamedSharding(mesh, P()),
            m=_shardings(mesh, param_specs(opt_sds.m)),
            v=_shardings(mesh, param_specs(opt_sds.v)),
        )
        batch_args, batch_specs = sp.batch_sds(cfg, cell, mesh)
        bsh = {k: NamedSharding(mesh, v) for k, v in batch_specs.items()}
        fn = jax.jit(
            step,
            in_shardings=(psh, osh, bsh),
            donate_argnums=(0, 1),
        )
        return fn.lower(params_sds, opt_sds, batch_args)

    from repro.parallel.sharding import dp_axes

    dp = dp_axes(mesh)
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    b = cell.global_batch
    bspec3 = P(dp, None, None) if b % dp_size == 0 else P(None, None, None)

    # enc-dec archs decode against a fixed encoder memory (cross-attention)
    mem_sds = None
    if cfg.encoder_layers:
        mem_sds = jax.ShapeDtypeStruct((b, cell.seq_len, cfg.d_model), jnp.dtype(cfg.dtype))
    fe_sds = None
    if cfg.frontend == "vision" and cell.mode == "prefill":
        fe_sds = jax.ShapeDtypeStruct((b, cfg.frontend_tokens, cfg.d_model), jnp.float32)

    if cell.mode == "prefill":
        # prefill: cached forward, last-position logits only
        caches_sds, cspecs, seq_shard = sp.cache_sds(cfg, cell, mesh)
        csh = _shardings(mesh, cspecs)
        s = cell.seq_len
        tok_spec = P(dp, None) if b % dp_size == 0 else P(None, None)
        toks = jax.ShapeDtypeStruct((b, s), jnp.int32)

        def prefill(params, tokens, caches, memory, frontend):
            logits, caches = tf.decode_step(
                params, cfg, tokens, caches, last_only=True,
                memory=memory, frontend_embeds=frontend,
                moe_impl=moe_impl, ep_info=ep_info,
            )
            return logits, caches

        # None-valued optional inputs are baked via closures (jit can't take
        # None leaves with shardings)
        if mem_sds is None and fe_sds is None:
            fn = jax.jit(
                lambda params, tokens, caches: prefill(params, tokens, caches, None, None),
                in_shardings=(psh, NamedSharding(mesh, tok_spec), csh),
                out_shardings=(NamedSharding(mesh, P()), csh),
                donate_argnums=(2,),
            )
            return fn.lower(params_sds, toks, caches_sds)
        if mem_sds is not None and fe_sds is None:
            fn = jax.jit(
                lambda params, tokens, caches, memory: prefill(params, tokens, caches, memory, None),
                in_shardings=(psh, NamedSharding(mesh, tok_spec), csh, NamedSharding(mesh, bspec3)),
                out_shardings=(NamedSharding(mesh, P()), csh),
                donate_argnums=(2,),
            )
            return fn.lower(params_sds, toks, caches_sds, mem_sds)
        fn = jax.jit(
            lambda params, tokens, caches, frontend: prefill(params, tokens, caches, None, frontend),
            in_shardings=(psh, NamedSharding(mesh, tok_spec), csh, NamedSharding(mesh, bspec3)),
            out_shardings=(NamedSharding(mesh, P()), csh),
            donate_argnums=(2,),
        )
        return fn.lower(params_sds, toks, caches_sds, fe_sds)

    assert cell.mode == "decode"
    caches_sds, cspecs, seq_shard = sp.cache_sds(cfg, cell, mesh)
    csh = _shardings(mesh, cspecs)
    tok_sds, tok_spec = sp.decode_tokens_sds(cell, mesh, seq_shard)

    if cfg.encoder_layers:
        def serve_step_mem(params, tokens, caches, memory):
            logits, caches = tf.decode_step(
                params, cfg, tokens, caches, last_only=True, memory=memory
            )
            nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
            return nxt[:, None], caches

        fn = jax.jit(
            serve_step_mem,
            in_shardings=(psh, NamedSharding(mesh, tok_spec), csh, NamedSharding(mesh, bspec3)),
            out_shardings=(NamedSharding(mesh, tok_spec), csh),
            donate_argnums=(2,),
        )
        return fn.lower(params_sds, tok_sds, caches_sds, mem_sds)

    def serve_step(params, tokens, caches):
        logits, caches = tf.decode_step(params, cfg, tokens, caches, last_only=True,
                                        moe_impl=moe_impl, ep_info=ep_info)
        nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        return nxt[:, None], caches

    fn = jax.jit(
        serve_step,
        in_shardings=(psh, NamedSharding(mesh, tok_spec), csh),
        out_shardings=(NamedSharding(mesh, tok_spec), csh),
        donate_argnums=(2,),
    )
    return fn.lower(params_sds, tok_sds, caches_sds)


def _unrolled_sibling(cfg: ModelConfig, k: int) -> ModelConfig:
    """A k-scan-iteration sibling with every scan unrolled, for cost
    extrapolation (XLA cost_analysis counts while bodies ONCE — see the
    calibration note in EXPERIMENTS.md §Roofline)."""
    import dataclasses

    if cfg.family == "hybrid":
        return dataclasses.replace(cfg, n_layers=cfg.attn_every * k, scan_unroll=True)
    if cfg.encoder_layers:
        return dataclasses.replace(cfg, n_layers=k, encoder_layers=k, scan_unroll=True)
    return dataclasses.replace(cfg, n_layers=k, scan_unroll=True)


def _scan_scale(cfg: ModelConfig) -> float:
    """Real scan trip count the k=1 body must be scaled to."""
    if cfg.family == "hybrid":
        return cfg.n_layers / cfg.attn_every
    return float(cfg.n_layers)


def _measure(mesh, cfg, cell, variant=None):
    lowered = lower_cell(mesh, cfg, cell, variant)
    compiled = lowered.compile()
    cost = compiled.cost_analysis()
    coll = collective_bytes(compiled.as_text())
    return compiled, cost, coll


def extrapolated_cost(mesh, cfg: ModelConfig, cell: ShapeCell, variant=None):
    """total(L) = fixed + L·body via two small unrolled compiles."""
    _, c1, k1 = _measure(mesh, _unrolled_sibling(cfg, 1), cell, variant)
    _, c2, k2 = _measure(mesh, _unrolled_sibling(cfg, 2), cell, variant)
    scale = _scan_scale(cfg)

    def extrap(a, b):
        body = max(b - a, 0.0)
        fixed = max(a - body, 0.0)
        return fixed + scale * body

    cost = {
        k: extrap(float(c1.get(k, 0.0) or 0.0), float(c2.get(k, 0.0) or 0.0))
        for k in ("flops", "bytes accessed", "transcendentals")
    }
    coll = {
        k: extrap(float(k1.get(k, 0)), float(k2.get(k, 0)))
        for k in k1
        if k != "counts"
    }
    return cost, coll


def run_cell(arch: str, shape: str, *, multi_pod: bool = False, verbose: bool = True,
             with_cost: bool = True, variant: str | None = None):
    cfg = get_config(arch)
    cell = SHAPES[shape]
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    chips = 512 if multi_pod else 256
    t0 = time.time()
    with mesh:
        lowered = lower_cell(mesh, cfg, cell, variant)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        raw_cost = compiled.cost_analysis()
        raw_coll = collective_bytes(compiled.as_text())
        if with_cost:
            cost, coll = extrapolated_cost(mesh, cfg, cell, variant)
        else:
            cost, coll = raw_cost, raw_coll
    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    terms = derive_terms(
        arch=arch,
        shape=shape,
        mesh_name=mesh_name,
        chips=chips,
        cost=cost,
        coll=coll,
        model_flops=model_flops_estimate(cfg, cell),
    )
    result = {
        "arch": arch,
        "shape": shape,
        "mesh": mesh_name,
        "mode": cell.mode,
        "variant": variant,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": mem.peak_memory_in_bytes if mem else None,
        },
        "cost": {k: cost.get(k) for k in ("flops", "bytes accessed", "transcendentals")},
        "cost_raw_scanned": {
            k: raw_cost.get(k) for k in ("flops", "bytes accessed", "transcendentals")
        },
        "collectives": coll,
        "collectives_raw_scanned": raw_coll,
        "roofline": terms.to_dict(),
    }
    if verbose:
        ma = result["memory"]
        print(
            f"[{arch} × {shape} × {mesh_name}] OK  "
            f"lower {t_lower:.0f}s compile {t_compile:.0f}s  "
            f"peak/dev {(ma['peak_bytes'] or 0)/2**30:.2f} GiB  "
            f"flops {terms.hlo_flops:.3e}  coll {terms.coll_bytes:.3e} B  "
            f"bottleneck={terms.bottleneck}",
            flush=True,
        )
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--variant", default=None,
                    help="§Perf variant: twobuf | moe_ts | ticketed")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    cells = []
    if args.all:
        for arch in ARCH_IDS:
            cfg = get_config(arch)
            for cell in applicable_shapes(cfg):
                cells.append((arch, cell.name))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    failures = []
    for arch, shape in cells:
        for mp in meshes:
            tag = f"{arch}_{shape}_{'2x16x16' if mp else '16x16'}"
            if args.variant:
                tag += f"__{args.variant}"
            out_path = os.path.join(args.out, tag + ".json")
            if os.path.exists(out_path):
                print(f"[{tag}] cached, skipping", flush=True)
                continue
            try:
                res = run_cell(arch, shape, multi_pod=mp, variant=args.variant)
                with open(out_path, "w") as f:
                    json.dump(res, f, indent=1)
            except Exception as e:  # noqa: BLE001 — report and continue
                failures.append((tag, repr(e)))
                print(f"[{tag}] FAILED: {e}", flush=True)
                traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for tag, err in failures:
            print(" ", tag, err)
        raise SystemExit(1)
    print("\nall dry-run cells passed")


if __name__ == "__main__":
    main()
