"""Model assembly: decoder-only LMs, MoE LMs, enc-dec, hybrid SSM, RWKV.

Layer stacks are **scanned** (params stacked on a leading L axis,
``jax.lax.scan`` over the stack with ``jax.checkpoint`` on the block body).
This keeps HLO size O(1) in depth — required to lower 26–48-layer models on
512 simulated devices in reasonable compile time — and gives the standard
remat-per-layer memory profile.

Heterogeneous stacks (zamba2) scan over *super-blocks* of (attn_every−1
Mamba2 layers + one shared-weight attention block); the shared attention
parameters live outside the scanned pytree, exactly matching zamba2's
weight sharing.

All forward paths return ``(logits, aux)`` where aux carries MoE aux losses
and (in cached mode) the updated caches.
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.models import moe as moe_lib
from repro.models import rwkv as rwkv_lib
from repro.models import ssm as ssm_lib
from repro.models.attention import KVCache, attn_init, make_cache, multihead_attention
from repro.models.config import ModelConfig
from repro.models.layers import (
    Params,
    apply_norm,
    dense,
    dense_init,
    embed,
    embedding_init,
    mlp,
    mlp_init,
    norm_init,
    softcap,
    ticketed_embed,
)
from jax import shard_map


# ---------------------------------------------------------------------------
# per-layer blocks
# ---------------------------------------------------------------------------

def _attn_block_init(key, cfg: ModelConfig, cross: bool = False) -> Params:
    ks = jax.random.split(key, 8)
    p = {
        "ln_attn": norm_init(cfg.norm_kind, cfg.d_model),
        "attn": attn_init(ks[0], cfg),
        "ln_mlp": norm_init(cfg.norm_kind, cfg.d_model),
    }
    if cfg.post_block_norm:
        p["ln_attn_post"] = norm_init(cfg.norm_kind, cfg.d_model)
        p["ln_mlp_post"] = norm_init(cfg.norm_kind, cfg.d_model)
    if cross:
        p["ln_cross"] = norm_init(cfg.norm_kind, cfg.d_model)
        p["cross"] = attn_init(ks[1], cfg, cross=True)
    if cfg.moe_num_experts:
        p["moe"] = moe_lib.moe_init(ks[2], cfg)
    else:
        p["mlp"] = mlp_init(ks[3], cfg.d_model, cfg.d_ff, cfg.mlp_kind)
    return p


def _attn_block(
    p: Params,
    cfg: ModelConfig,
    x,
    *,
    window=None,
    cache: KVCache | None = None,
    memory=None,
    positions=None,
    moe_impl: str = "dense",
    ep_info: dict | None = None,
):
    h = apply_norm(cfg.norm_kind, p["ln_attn"], x)
    a, new_cache = multihead_attention(
        p["attn"], cfg, h, window=window, cache=cache, positions=positions
    )
    if cfg.post_block_norm:
        a = apply_norm(cfg.norm_kind, p["ln_attn_post"], a)
    x = x + a * cfg.residual_multiplier

    if memory is not None:
        hc = apply_norm(cfg.norm_kind, p["ln_cross"], x)
        cattn, _ = multihead_attention(p["cross"], cfg, hc, memory=memory, causal=False)
        x = x + cattn * cfg.residual_multiplier

    h = apply_norm(cfg.norm_kind, p["ln_mlp"], x)
    aux = jnp.zeros((), jnp.float32)
    if "moe" in p:
        if moe_impl == "ep":
            m, aux = _moe_ep_shardmapped(p["moe"], cfg, h, ep_info)
        else:
            m, aux = moe_lib.moe_mlp_dense(p["moe"], cfg, h)
    else:
        m = mlp(p["mlp"], h, cfg.mlp_kind)
    if cfg.post_block_norm:
        m = apply_norm(cfg.norm_kind, p["ln_mlp_post"], m)
    x = x + m * cfg.residual_multiplier
    return x, new_cache, aux


def _moe_ep_shardmapped(p_moe: Params, cfg: ModelConfig, h, ep_info: dict):
    """Expert parallelism: run moe_mlp_ep under shard_map — experts sharded
    over 'model', tokens over the data axes, dispatch/return via explicit
    all_to_all (models/moe.py).  ``ep_info`` = {mesh, dp (axis tuple),
    capacity_per_expert, token_slice}.  The paper connection: the
    sender-side dispatch IS the partitioned group-by (radix partition by
    expert owner + fixed buckets + exchange).

    token_slice (§Perf iteration 2 in EXPERIMENTS.md): activations enter
    replicated over 'model', so a naive EP dispatch sends 16 identical
    copies of every token (useful-FLOPs fraction ≈ 1/16).  With
    token_slice=True each model peer dispatches only its 1/16 token slice
    (sequence parallelism for the MoE block) and the outputs all-gather
    back — removing the 16× redundant expert compute and a2a traffic at the
    cost of one (T_local/16 → T_local) all-gather of d_model activations.
    """
    from jax.sharding import PartitionSpec as P

    mesh = ep_info["mesh"]
    dp = ep_info["dp"]
    cap = ep_info["capacity_per_expert"]
    token_slice = ep_info.get("token_slice", False)
    quantize_dispatch = ep_info.get("quantize_dispatch", False)
    num_shards = mesh.shape["model"]

    moe_specs = {
        "router": jax.tree.map(lambda _: P(), p_moe["router"]),
        "w_gate": P("model", None, None),
        "w_up": P("model", None, None),
        "w_down": P("model", None, None),
    }
    if "shared" in p_moe:
        moe_specs["shared"] = jax.tree.map(lambda _: P(), p_moe["shared"])
        moe_specs["shared_gate"] = jax.tree.map(lambda _: P(), p_moe["shared_gate"])

    def local_fn(pl, hl):
        b, s, d = hl.shape
        if not token_slice:
            out, aux = moe_lib.moe_mlp_ep(
                pl, cfg, hl, axis="model", num_shards=num_shards,
                capacity_per_expert=cap, quantize_dispatch=quantize_dispatch,
            )
            aux = jax.lax.pmean(aux, dp)
            return out, aux
        # token-sliced dispatch: this peer handles tokens [r·ts, (r+1)·ts)
        t = b * s
        ts = -(-t // num_shards)  # ceil for tiny decode batches
        x2 = hl.reshape(t, d)
        if ts * num_shards != t:
            x2 = jnp.concatenate(
                [x2, jnp.zeros((ts * num_shards - t, d), x2.dtype)]
            )
        rank = jax.lax.axis_index("model")
        xs = jax.lax.dynamic_slice_in_dim(x2, rank * ts, ts)
        out_s, aux = moe_lib.moe_mlp_ep(
            pl, cfg, xs[None], axis="model", num_shards=num_shards,
            capacity_per_expert=cap, quantize_dispatch=quantize_dispatch,
        )
        out = jax.lax.all_gather(out_s[0], "model", tiled=True)[:t]
        aux = jax.lax.pmean(aux, dp + ("model",))
        return out.reshape(b, s, d), aux

    fn = shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(moe_specs, P(dp, None, None)),
        out_specs=(P(dp, None, None), P()),
        check_vma=False,
    )
    return fn(p_moe, h)


def _mamba_block_init(key, cfg: ModelConfig) -> Params:
    return {
        "ln": norm_init(cfg.norm_kind, cfg.d_model),
        "mamba": ssm_lib.mamba2_init(key, cfg),
    }


def _mamba_block(p, cfg, x, cache=None):
    h = apply_norm(cfg.norm_kind, p["ln"], x)
    y, new_cache = ssm_lib.mamba2_block(p["mamba"], cfg, h, cache)
    return x + y * cfg.residual_multiplier, new_cache


def _rwkv_block_init(key, cfg: ModelConfig) -> Params:
    k1, k2 = jax.random.split(key)
    return {
        "ln1": norm_init(cfg.norm_kind, cfg.d_model),
        "ln2": norm_init(cfg.norm_kind, cfg.d_model),
        "time": rwkv_lib.rwkv6_init(k1, cfg),
    }


def _rwkv_block(p, cfg, x, cache=None):
    h = apply_norm(cfg.norm_kind, p["ln1"], x)
    y, cache = rwkv_lib.rwkv6_time_mix(p["time"], cfg, h, cache)
    x = x + y
    h = apply_norm(cfg.norm_kind, p["ln2"], x)
    y, cache = rwkv_lib.rwkv6_channel_mix(p["time"], h, cache)
    return x + y, cache


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _stack(trees):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def padded_vocab(v: int) -> int:
    """Embedding tables are padded to a multiple of 256 so the vocab dim
    shards over any 'model' axis size (49155/92553/256206 are not divisible
    by 16); logits are sliced back to the true vocab in _lm_logits."""
    return (v + 255) // 256 * 256


def init_params(key, cfg: ModelConfig) -> Params:
    keys = jax.random.split(key, cfg.n_layers + cfg.encoder_layers + 8)
    vpad = padded_vocab(cfg.vocab_size)
    p: Params = {"embed": embedding_init(keys[-1], vpad, cfg.d_model)}
    p["final_norm"] = norm_init(cfg.norm_kind, cfg.d_model)
    if not cfg.tie_embeddings:
        p["lm_head"] = dense_init(keys[-2], cfg.d_model, vpad)

    if cfg.family == "ssm":
        p["layers"] = _stack([_rwkv_block_init(keys[i], cfg) for i in range(cfg.n_layers)])
    elif cfg.family == "hybrid":
        per = cfg.attn_every - 1
        n_super = cfg.n_layers // cfg.attn_every
        rem = cfg.n_layers - n_super * cfg.attn_every
        supers = []
        ki = 0
        for _ in range(n_super):
            supers.append(
                _stack([_mamba_block_init(keys[ki + j], cfg) for j in range(per)])
            )
            ki += per
        p["super"] = _stack(supers)  # (n_super, per, ...)
        p["shared_attn"] = _attn_block_init(keys[ki], cfg)
        ki += 1
        if rem:
            p["tail"] = _stack([_mamba_block_init(keys[ki + j], cfg) for j in range(rem)])
    else:
        cross = cfg.encoder_layers > 0
        p["layers"] = _stack(
            [_attn_block_init(keys[i], cfg, cross=cross) for i in range(cfg.n_layers)]
        )
        if cfg.encoder_layers:
            enc_keys = keys[cfg.n_layers : cfg.n_layers + cfg.encoder_layers]
            p["encoder"] = {
                "layers": _stack([_attn_block_init(k, cfg) for k in enc_keys]),
                "final_norm": norm_init(cfg.norm_kind, cfg.d_model),
            }
    if cfg.frontend != "none":
        p["frontend_proj"] = dense_init(keys[-3], cfg.d_model, cfg.d_model)
    return p


def layer_windows(cfg: ModelConfig) -> jnp.ndarray | None:
    """Per-layer sliding windows: gemma2 alternates local/global."""
    if cfg.local_global_pattern and cfg.sliding_window:
        w = [cfg.sliding_window if i % 2 == 0 else -1 for i in range(cfg.n_layers)]
        return jnp.asarray(w, jnp.int32)
    if cfg.sliding_window:
        return jnp.full((cfg.n_layers,), cfg.sliding_window, jnp.int32)
    return None


# ---------------------------------------------------------------------------
# forward (train / prefill): no caches
# ---------------------------------------------------------------------------

class ForwardOut(NamedTuple):
    logits: jnp.ndarray
    aux_loss: jnp.ndarray


def _embed_tokens(p, cfg: ModelConfig, tokens, *, ticketed: bool, max_unique: int,
                  onehot: bool = False):
    dtype = jnp.dtype(cfg.dtype)
    if onehot:
        # decode path: a gather against the vocab-sharded table makes XLA
        # all-gather the WHOLE table (1.5 GB/step for qwen2.5); the one-hot
        # matmul keeps the table sharded and psums a (B, d) vector instead
        # — the paper's one-hot MXU strategy, applied to the lookup
        # (§Perf cell 1, iteration 5).
        table = p["embed"]["table"].astype(dtype)
        oh = jax.nn.one_hot(tokens.reshape(-1), table.shape[0], dtype=dtype)
        x = (oh @ table).reshape(*tokens.shape, -1)
    elif ticketed:
        from repro.core.hashing import table_capacity

        cap = table_capacity(max_unique)
        x = ticketed_embed(p["embed"]["table"], tokens, max_unique, cap).astype(dtype)
    else:
        x = embed(p["embed"], tokens, dtype)
    if cfg.emb_multiplier != 1.0:  # gemma2 √d scaling / granite multiplier
        x = x * jnp.asarray(cfg.emb_multiplier, dtype)
    return x


def _lm_logits(p, cfg: ModelConfig, x):
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", x, p["embed"]["table"].astype(x.dtype))
    else:
        logits = dense(p["lm_head"], x)
    logits = logits[..., : cfg.vocab_size]  # drop sharding-pad rows
    logits = logits * cfg.logits_multiplier
    return softcap(logits.astype(jnp.dtype(cfg.logits_dtype)), cfg.final_logit_softcap)


def _remat_policy(cfg):
    if cfg.remat_policy == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    return None  # full remat (save nothing)


def _run_attn_stack(p_layers, cfg, x, windows, memory=None, moe_impl="dense", ep_info=None):
    remat_block = jax.checkpoint(
        functools.partial(_attn_block, moe_impl=moe_impl, ep_info=ep_info),
        static_argnums=(1,),
        policy=_remat_policy(cfg),
    )

    def body(carry, scanned):
        x, aux = carry
        if windows is not None:
            pl, w = scanned
        else:
            pl, w = scanned, None
        x, _, a = remat_block(pl, cfg, x, window=w, memory=memory)
        return (x, aux + a), None

    scanned = (p_layers, windows) if windows is not None else p_layers
    (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), scanned, unroll=cfg.scan_unroll)
    return x, aux


def _run_hybrid_stack(p, cfg, x):
    remat_mamba = jax.checkpoint(_mamba_block, static_argnums=(1,))
    remat_attn = jax.checkpoint(_attn_block, static_argnums=(1,))
    per = cfg.attn_every - 1

    def super_body(x, p_super):
        for j in range(per):
            pj = jax.tree.map(lambda a: a[j], p_super)
            x, _ = remat_mamba(pj, cfg, x)
        x, _, _ = remat_attn(p["shared_attn"], cfg, x, window=cfg.sliding_window)
        return x, None

    x, _ = jax.lax.scan(super_body, x, p["super"], unroll=cfg.scan_unroll)
    if "tail" in p:
        def tail_body(x, pj):
            x, _ = remat_mamba(pj, cfg, x)
            return x, None
        x, _ = jax.lax.scan(tail_body, x, p["tail"], unroll=cfg.scan_unroll)
    return x, jnp.zeros((), jnp.float32)


def _run_rwkv_stack(p_layers, cfg, x):
    remat = jax.checkpoint(_rwkv_block, static_argnums=(1,))

    def body(x, pl):
        x, _ = remat(pl, cfg, x)
        return x, None

    x, _ = jax.lax.scan(body, x, p_layers, unroll=cfg.scan_unroll)
    return x, jnp.zeros((), jnp.float32)


def forward(
    params: Params,
    cfg: ModelConfig,
    batch: dict,
    *,
    ticketed_embedding: bool = True,
    moe_impl: str = "dense",
    ep_info: dict | None = None,
) -> ForwardOut:
    """Full-sequence forward.

    batch: tokens (B,S) [+ frontend_embeds (B,F,D)] [+ encoder_frames
    (B,Se,D) for enc-dec].
    """
    tokens = batch["tokens"]
    max_unique = min(cfg.vocab_size, tokens.shape[0] * tokens.shape[1])
    x = _embed_tokens(params, cfg, tokens, ticketed=ticketed_embedding, max_unique=max_unique)

    if cfg.frontend == "vision":
        # frontend STUB: precomputed patch embeddings replace the first F
        # token positions (input_specs supplies them; the ViT itself is out
        # of scope per the assignment).
        vis = dense(params["frontend_proj"], batch["frontend_embeds"].astype(x.dtype))
        f = vis.shape[1]
        x = jnp.concatenate([vis, x[:, f:, :]], axis=1)

    memory = None
    if cfg.encoder_layers:
        enc_in = dense(params["frontend_proj"], batch["encoder_frames"].astype(x.dtype))
        mem, _ = _run_attn_stack(params["encoder"]["layers"], cfg, enc_in, None)
        memory = apply_norm(cfg.norm_kind, params["encoder"]["final_norm"], mem)

    windows = layer_windows(cfg)
    if cfg.family == "ssm":
        x, aux = _run_rwkv_stack(params["layers"], cfg, x)
    elif cfg.family == "hybrid":
        x, aux = _run_hybrid_stack(params, cfg, x)
    else:
        x, aux = _run_attn_stack(
            params["layers"], cfg, x, windows, memory=memory,
            moe_impl=moe_impl, ep_info=ep_info,
        )

    x = apply_norm(cfg.norm_kind, params["final_norm"], x)
    return ForwardOut(_lm_logits(params, cfg, x), aux)


# ---------------------------------------------------------------------------
# decode (cached, one token)
# ---------------------------------------------------------------------------

def init_caches(cfg: ModelConfig, batch: int, max_len: int, dtype) -> Any:
    if cfg.family == "ssm":
        one = rwkv_lib.make_rwkv_cache(cfg, batch, dtype)
        return jax.tree.map(lambda x: jnp.broadcast_to(x, (cfg.n_layers, *x.shape)), one)
    if cfg.family == "hybrid":
        per = cfg.attn_every - 1
        n_super = cfg.n_layers // cfg.attn_every
        rem = cfg.n_layers - n_super * cfg.attn_every
        ssm_one = ssm_lib.make_ssm_cache(cfg, batch, dtype)
        caches = {
            "super_ssm": jax.tree.map(
                lambda x: jnp.broadcast_to(x, (n_super, per, *x.shape)), ssm_one
            ),
            "attn": jax.tree.map(
                lambda x: jnp.broadcast_to(x, (n_super, *x.shape)),
                make_cache(cfg, batch, max_len, dtype),
            ),
        }
        if rem:
            caches["tail_ssm"] = jax.tree.map(
                lambda x: jnp.broadcast_to(x, (rem, *x.shape)), ssm_one
            )
        return caches
    one = make_cache(cfg, batch, max_len, dtype)
    return jax.tree.map(lambda x: jnp.broadcast_to(x, (cfg.n_layers, *x.shape)), one)


def decode_step(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,  # (B, S) — S=1 for decode, S>1 for cached prefill
    caches,
    *,
    memory=None,
    moe_impl: str = "dense",
    ep_info: dict | None = None,
    last_only: bool = False,
    frontend_embeds=None,
):
    """Cached step. S=1 → one-token decode; S>1 → prefill THROUGH the cache
    (attention appends K/V in place; SSM/RWKV run the chunked path seeded
    from the cached state).  ``last_only`` computes logits for the final
    position only — mandatory for long prefills where (B,S,V) logits would
    dwarf everything else.  ``memory`` feeds enc-dec cross-attention;
    ``frontend_embeds`` (VLM prefill) replaces the first F positions.
    Returns (logits, new_caches)."""
    x = _embed_tokens(params, cfg, tokens, ticketed=False, max_unique=1)
    if frontend_embeds is not None:
        vis = dense(params["frontend_proj"], frontend_embeds.astype(x.dtype))
        x = jnp.concatenate([vis, x[:, vis.shape[1]:, :]], axis=1)
    windows = layer_windows(cfg)

    if cfg.family == "ssm":
        def rwkv_body(x, pc):
            pl, cache = pc
            x, cache = _rwkv_block(pl, cfg, x, cache)
            return x, cache

        x, new_caches = jax.lax.scan(rwkv_body, x, (params["layers"], caches), unroll=cfg.scan_unroll)
    elif cfg.family == "hybrid":
        per = cfg.attn_every - 1

        def super_body(x, scanned):
            p_super, ssm_c, attn_c = scanned
            new_ssm = []
            for j in range(per):
                pj = jax.tree.map(lambda a: a[j], p_super)
                cj = jax.tree.map(lambda a: a[j], ssm_c)
                x, cj = _mamba_block(pj, cfg, x, cj)
                new_ssm.append(cj)
            new_ssm = jax.tree.map(lambda *xs: jnp.stack(xs), *new_ssm)
            x, attn_c_new, _ = _attn_block(
                params["shared_attn"], cfg, x, window=cfg.sliding_window, cache=attn_c
            )
            return x, (new_ssm, attn_c_new)

        x, (new_super_ssm, new_attn) = jax.lax.scan(
            super_body, x, (params["super"], caches["super_ssm"], caches["attn"]),
            unroll=cfg.scan_unroll,
        )
        new_caches = {"super_ssm": new_super_ssm, "attn": new_attn}
        if "tail" in params:
            def tail_body(x, pc):
                pj, cj = pc
                x, cj = _mamba_block(pj, cfg, x, cj)
                return x, cj
            x, new_tail = jax.lax.scan(tail_body, x, (params["tail"], caches["tail_ssm"]), unroll=cfg.scan_unroll)
            new_caches["tail_ssm"] = new_tail
    else:
        def body(x, scanned):
            if windows is not None:
                pl, cache, w = scanned
            else:
                (pl, cache), w = scanned, None
            x, new_cache, _ = _attn_block(
                pl, cfg, x, window=w, cache=cache, memory=memory,
                moe_impl=moe_impl, ep_info=ep_info,
            )
            return x, new_cache

        scanned = (
            (params["layers"], caches, windows)
            if windows is not None
            else (params["layers"], caches)
        )
        x, new_caches = jax.lax.scan(body, x, scanned, unroll=cfg.scan_unroll)

    if last_only:
        x = x[:, -1:, :]
    x = apply_norm(cfg.norm_kind, params["final_norm"], x)
    return _lm_logits(params, cfg, x), new_caches


# ---------------------------------------------------------------------------
# two-buffer decode (§Perf iteration 1): frozen sharded prefix + small
# replicated tail — see attention.twobuf_attention
# ---------------------------------------------------------------------------

def init_twobuf_caches(cfg: ModelConfig, batch: int, prefix_len: int, tail_len: int, dtype):
    from repro.models.attention import make_cache

    prefix = make_cache(cfg, batch, prefix_len, dtype)._replace(
        length=jnp.full((), prefix_len, jnp.int32)
    )
    tail = make_cache(cfg, batch, tail_len, dtype)
    stack = lambda c: jax.tree.map(lambda x: jnp.broadcast_to(x, (cfg.n_layers, *x.shape)), c)
    return stack(prefix), stack(tail)


def decode_step_twobuf(params: Params, cfg: ModelConfig, tokens, prefix_caches, tail_caches):
    """One-token decode against (prefix, tail) caches. Attention-family
    archs only (the SSM/hybrid families have O(1) states and no cache
    movement problem to fix)."""
    from repro.models.attention import twobuf_attention

    assert cfg.family in ("dense", "moe", "vlm"), cfg.family
    x = _embed_tokens(params, cfg, tokens, ticketed=False, max_unique=1, onehot=True)
    windows = layer_windows(cfg)

    def body(x, scanned):
        if windows is not None:
            pl, pref, tl, w = scanned
        else:
            (pl, pref, tl), w = scanned, None
        h = apply_norm(cfg.norm_kind, pl["ln_attn"], x)
        a, new_tail = twobuf_attention(pl["attn"], cfg, h, pref, tl, window=w)
        if cfg.post_block_norm:
            a = apply_norm(cfg.norm_kind, pl["ln_attn_post"], a)
        x = x + a * cfg.residual_multiplier
        h = apply_norm(cfg.norm_kind, pl["ln_mlp"], x)
        if "moe" in pl:
            m, _ = moe_lib.moe_mlp_dense(pl["moe"], cfg, h)
        else:
            m = mlp(pl["mlp"], h, cfg.mlp_kind)
        if cfg.post_block_norm:
            m = apply_norm(cfg.norm_kind, pl["ln_mlp_post"], m)
        x = x + m * cfg.residual_multiplier
        return x, new_tail

    scanned = (
        (params["layers"], prefix_caches, tail_caches, windows)
        if windows is not None
        else (params["layers"], prefix_caches, tail_caches)
    )
    x, new_tails = jax.lax.scan(body, x, scanned, unroll=cfg.scan_unroll)
    x = apply_norm(cfg.norm_kind, params["final_norm"], x)
    return _lm_logits(params, cfg, x), new_tails


# ---------------------------------------------------------------------------
# loss
# ---------------------------------------------------------------------------

def lm_loss(params, cfg: ModelConfig, batch, **fw_kwargs):
    out = forward(params, cfg, batch, **fw_kwargs)
    logits = out.logits  # fp32 (B,S,V)
    targets = batch["targets"]
    mask = (targets >= 0).astype(jnp.float32)
    tgt = jnp.maximum(targets, 0)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, tgt[..., None], axis=-1)[..., 0]
    nll = (logz - gold) * mask
    loss = jnp.sum(nll) / jnp.maximum(jnp.sum(mask), 1.0)
    return loss + out.aux_loss, {"nll": loss, "aux": out.aux_loss}
