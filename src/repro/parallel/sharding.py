"""Sharding rules: param-name → PartitionSpec, batch/cache specs.

Megatron-style TP over the 'model' axis + DP over ('pod','data'):

  * embedding table & lm_head: vocab-sharded over 'model' (keeps the huge
    (B,S,V) logits vocab-sharded through the loss; the softmax statistics
    travel, not the logits),
  * attention: fan-out projections column-sharded (heads), wo row-sharded,
  * MLP: w_in column-, w_down row-sharded,
  * MoE experts: expert-TP — per-expert hidden F sharded over 'model'
    (works for any expert count; the EP all_to_all path in models/moe.py is
    the shard_map alternative, exercised where E % shards == 0),
  * Mamba2: d_inner projections column-sharded, state projections (B/C)
    replicated, per-head params sharded, out row-sharded,
  * RWKV6: head-dim projections column-sharded, wo row-sharded,
  * norms/scalars: replicated.

Stacked-layer params carry leading scan axes; specs are right-aligned
(left-padded with None) to the leaf rank, so one table covers plain,
scanned (L,...) and hybrid (n_super, per, ...) layouts.
"""
from __future__ import annotations

import re
from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.models.config import ModelConfig

M = "model"

# ordered (regex over '/'-joined path, base spec for the *trailing* dims)
_RULES: list[tuple[str, P]] = [
    (r"embed/table$", P(M, None)),
    (r"lm_head/w$", P(None, M)),
    (r"frontend_proj/w$", P(None, None)),
    # attention
    (r"attn/wq/w$", P(None, M)),
    (r"attn/wk/w$", P(None, M)),
    (r"attn/wv/w$", P(None, M)),
    (r"attn/w[qkv]/b$", P(M)),
    (r"attn/wo/w$", P(M, None)),
    (r"attn/[qk]_norm/scale$", P(None)),
    (r"cross/wq/w$", P(None, M)),
    (r"cross/wk/w$", P(None, M)),
    (r"cross/wv/w$", P(None, M)),
    (r"cross/w[qkv]/b$", P(M)),
    (r"cross/wo/w$", P(M, None)),
    # dense mlp
    (r"mlp/w_gate/w$", P(None, M)),
    (r"mlp/w_up/w$", P(None, M)),
    (r"mlp/w_down/w$", P(M, None)),
    # moe (EP: experts sharded over 'model'; dispatch via all_to_all in
    # models/moe.py — the shard_map expert-parallel path)
    (r"moe/router/w$", P(None, None)),
    (r"moe/w_gate$", P(M, None, None)),
    (r"moe/w_up$", P(M, None, None)),
    (r"moe/w_down$", P(M, None, None)),
    (r"moe/shared/w_gate/w$", P(None, M)),
    (r"moe/shared/w_up/w$", P(None, M)),
    (r"moe/shared/w_down/w$", P(M, None)),
    (r"moe/shared_gate/w$", P(None, None)),
    # mamba2
    (r"mamba/in_z/w$", P(None, M)),
    (r"mamba/in_x/w$", P(None, M)),
    (r"mamba/in_B/w$", P(None, None)),
    (r"mamba/in_C/w$", P(None, None)),
    (r"mamba/in_dt/w$", P(None, M)),
    (r"mamba/conv_x$", P(None, M)),
    (r"mamba/conv_x_b$", P(M)),
    (r"mamba/conv_[BC]$", P(None, None)),
    (r"mamba/conv_[BC]_b$", P(None)),
    (r"mamba/A_log$", P(M)),
    (r"mamba/D$", P(M)),
    (r"mamba/dt_bias$", P(M)),
    (r"mamba/norm/scale$", P(M)),
    (r"mamba/out_proj/w$", P(M, None)),
    # rwkv6
    (r"time/w[rkvg]/w$", P(None, M)),
    (r"time/wo/w$", P(M, None)),
    (r"time/wA/w$", P(None, None)),
    (r"time/wB/w$", P(None, M)),
    (r"time/w0$", P(M)),
    (r"time/u$", P(M, None)),
    (r"time/mix_\w+$", P(None)),
    (r"time/ln_x/scale$", P(M)),
    (r"time/wk_c/w$", P(None, M)),
    (r"time/wv_c/w$", P(M, None)),
    (r"time/wr_c/w$", P(None, None)),
    # norms and anything else: replicated
    (r".*", P()),
]


def _path_str(path) -> str:
    parts = []
    for k in path:
        if isinstance(k, jax.tree_util.DictKey):
            parts.append(str(k.key))
        elif isinstance(k, jax.tree_util.SequenceKey):
            parts.append(str(k.idx))
        elif isinstance(k, jax.tree_util.GetAttrKey):
            parts.append(str(k.name))
        else:
            parts.append(str(k))
    return "/".join(parts)


def spec_for_path(path: str, ndim: int, shape=None) -> P:
    # int8-quantized kernels reuse the fp kernel's rule
    path = path.replace("/w_q8", "/w").replace("/w_scale", "/w")
    for pat, base in _RULES:
        if re.search(pat, path):
            spec = list(base)
            if len(spec) > ndim:  # scalar params matched by a vector rule
                spec = spec[-ndim:] if ndim else []
            # left-pad with None for scan axes
            spec = [None] * (ndim - len(spec)) + spec
            if shape is not None:  # size-1 dims (e.g. quant scales) can't shard
                spec = [a if shape[i] != 1 else None for i, a in enumerate(spec)]
            return P(*spec)
    return P()


def param_specs(params: Any) -> Any:
    """PartitionSpec pytree matching ``params``."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: spec_for_path(
            _path_str(path), np.ndim(leaf), getattr(leaf, "shape", None)
        ),
        params,
    )


def param_shardings(mesh: Mesh, params: Any) -> Any:
    return jax.tree.map(
        lambda spec: NamedSharding(mesh, spec), param_specs(params)
    )


def dp_axes(mesh: Mesh):
    """Data-parallel mesh axes: ('pod','data') multi-pod, ('data',) single."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def batch_spec(mesh: Mesh) -> P:
    return P(dp_axes(mesh), None)


def cache_specs(mesh: Mesh, cfg: ModelConfig, caches: Any) -> Any:
    """KV/SSM cache specs for decode. KV heads shard over 'model' when
    divisible; otherwise the cache SEQUENCE dim is model-sharded
    (flash-decoding layout: per-shard partial softmax stats travel, the 32k+
    cache never moves)."""
    dp = dp_axes(mesh)
    msize = mesh.shape[M]

    def spec(path, leaf):
        ps = _path_str(path)
        leaf_name = ps.split("/")[-1]
        nd = np.ndim(leaf)
        if leaf is None or nd == 0:
            return P()
        if "length" in ps:
            return P()
        if leaf_name in ("k", "v"):
            # (L, B, S, KV, hd) or (n_super, B, S, KV, hd)
            if cfg.n_kv_heads % msize == 0:
                return P(*([None] * (nd - 4)), dp, None, M, None)
            return P(*([None] * (nd - 4)), dp, M, None, None)
        if "state" in ps:  # SSM/RWKV state (..., B, H, hd, N)
            return P(*([None] * (nd - 4)), dp, M, None, None)
        if "conv" in ps and nd >= 3:   # (..., B, K-1, C) conv tails
            return P(*([None] * (nd - 3)), dp, None, None)
        if "last_x" in ps and nd >= 2:  # (..., B, D) token-shift tails
            return P(*([None] * (nd - 2)), dp, None)
        return P()  # anything unrecognized stays replicated (safe default)

    return jax.tree_util.tree_map_with_path(spec, caches)
