"""Training step + loop.

Two execution modes:

* ``pjit`` (default, used by the dry-run and real meshes): one jitted step
  with explicit in/out shardings, donated params/opt-state, XLA-overlapped
  gradient collectives (latency-hiding scheduler decomposes the psums into
  reduce-scatter/all-gather interleaved with the backward).

* ``manual_dp`` (shard_map over the data axes; CPU-testable): per-device
  grads, explicit fp32 psum over 'data' and — when ``grad_compression=
  "int8"`` — an int8 block-quantized psum over the slow 'pod' axis
  (optim/compression.py).  This is the distributed-optimization path that
  makes cross-pod scaling viable; the pjit path keeps fp32 everywhere.

The loop adds the framework-level fault tolerance: checkpoint-every-N with
atomic commits, auto-resume, and (host-level) straggler re-dispatch hooks.
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.models import transformer as tf
from repro.models.config import ModelConfig
from repro.optim import adamw
from repro.optim.clip import clip_by_global_norm
from repro.optim.compression import compressed_psum
from repro.optim.schedules import warmup_cosine
from repro.parallel.sharding import batch_spec, dp_axes, param_shardings, param_specs
from jax import shard_map


@dataclass(frozen=True)
class TrainHParams:
    peak_lr: float = 3e-4
    warmup: int = 100
    total_steps: int = 10_000
    clip_norm: float = 1.0
    weight_decay: float = 0.1
    ticketed_embedding: bool = True
    grad_compression: str | None = None  # None | "int8" (manual_dp mode)


def make_loss_fn(cfg: ModelConfig, hp: TrainHParams, *, moe_impl="dense", ep_info=None) -> Callable:
    def loss_fn(params, batch):
        return tf.lm_loss(
            params, cfg, batch, ticketed_embedding=hp.ticketed_embedding,
            moe_impl=moe_impl, ep_info=ep_info,
        )

    return loss_fn


def make_train_step(cfg: ModelConfig, hp: TrainHParams, *, moe_impl="dense", ep_info=None):
    """Returns train_step(params, opt_state, batch) -> (params, opt, metrics)."""
    loss_fn = make_loss_fn(cfg, hp, moe_impl=moe_impl, ep_info=ep_info)

    def train_step(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
        grads, gnorm = clip_by_global_norm(grads, hp.clip_norm)
        lr = warmup_cosine(
            opt_state.step, peak_lr=hp.peak_lr, warmup=hp.warmup, total=hp.total_steps
        )
        opt_state, params = adamw.update(
            opt_state, grads, params, lr=lr, weight_decay=hp.weight_decay
        )
        out_metrics = {
            "loss": loss,
            "nll": metrics["nll"],
            "aux": metrics["aux"],
            "grad_norm": gnorm,
            "lr": lr,
        }
        return params, opt_state, out_metrics

    return train_step


def jit_train_step(mesh, cfg: ModelConfig, hp: TrainHParams, params, opt_state):
    """pjit-compiled step with explicit shardings + donation."""
    pspecs = param_specs(params)
    ospecs = adamw.AdamWState(
        step=P(), m=param_specs(opt_state.m), v=param_specs(opt_state.v)
    )
    bspec = {"tokens": batch_spec(mesh), "targets": batch_spec(mesh)}
    # modality extras
    bspec_extra = {
        "frontend_embeds": P(dp_axes(mesh), None, None),
        "encoder_frames": P(dp_axes(mesh), None, None),
    }

    def shard(tree, specs):
        return jax.tree.map(lambda s: NamedSharding(mesh, s), specs)

    step = make_train_step(cfg, hp)

    def in_shardings(batch_tree):
        bs = {k: bspec.get(k, bspec_extra.get(k, P())) for k in batch_tree}
        return (
            shard(params, pspecs),
            jax.tree.map(lambda s: NamedSharding(mesh, s), ospecs),
            {k: NamedSharding(mesh, v) for k, v in bs.items()},
        )

    def compile_step(batch_tree):
        ish = in_shardings(batch_tree)
        osh = (
            ish[0],
            ish[1],
            {k: NamedSharding(mesh, P()) for k in ["loss", "nll", "aux", "grad_norm", "lr"]},
        )
        return jax.jit(
            step, in_shardings=ish, out_shardings=osh, donate_argnums=(0, 1)
        )

    return compile_step


def make_manual_dp_step(mesh, cfg: ModelConfig, hp: TrainHParams):
    """shard_map data-parallel step with explicit (optionally compressed)
    gradient all-reduce. Params replicated; batch sharded over dp axes."""
    loss_fn = make_loss_fn(cfg, hp)
    dp = dp_axes(mesh)

    def local_step(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
        # explicit gradient sync: fp32 over fast axis, int8 over 'pod'
        grads = jax.tree.map(lambda g: jax.lax.pmean(g, dp[-1]), grads)
        if "pod" in dp and hp.grad_compression == "int8":
            nshards = jax.lax.psum(jnp.ones(()), "pod")
            grads = jax.tree.map(
                lambda g: compressed_psum(g, "pod") / nshards, grads
            )
        elif "pod" in dp:
            grads = jax.tree.map(lambda g: jax.lax.pmean(g, "pod"), grads)
        grads, gnorm = clip_by_global_norm(grads, hp.clip_norm)
        lr = warmup_cosine(
            opt_state.step, peak_lr=hp.peak_lr, warmup=hp.warmup, total=hp.total_steps
        )
        opt_state, params = adamw.update(
            opt_state, grads, params, lr=lr, weight_decay=hp.weight_decay
        )
        loss = jax.lax.pmean(loss, dp[-1])
        return params, opt_state, {"loss": loss, "grad_norm": gnorm, "lr": lr}

    def batch_specs(batch):
        return {k: P(dp, *([None] * (v.ndim - 1))) for k, v in batch.items()}

    def wrapped(params, opt_state, batch):
        fn = shard_map(
            local_step,
            mesh=mesh,
            in_specs=(
                jax.tree.map(lambda _: P(), params),
                jax.tree.map(lambda _: P(), opt_state),
                batch_specs(batch),
            ),
            out_specs=(
                jax.tree.map(lambda _: P(), params),
                jax.tree.map(lambda _: P(), opt_state),
                {"loss": P(), "grad_norm": P(), "lr": P()},
            ),
            check_vma=False,
        )
        return fn(params, opt_state, batch)

    return wrapped


def train_loop(
    mesh,
    cfg: ModelConfig,
    hp: TrainHParams,
    data_iter,
    *,
    steps: int,
    params=None,
    checkpoint_manager=None,
    checkpoint_every: int = 100,
    log_every: int = 10,
):
    """Host-side loop: data → step → metrics → periodic checkpoints.

    Resumes from the latest checkpoint if the manager has one (fault
    tolerance: a killed run restarts bit-exact from the last commit).
    """
    if params is None:
        params = tf.init_params(jax.random.PRNGKey(0), cfg)
    opt_state = adamw.init(params)
    start_step = 0
    if checkpoint_manager is not None:
        restored = checkpoint_manager.restore_latest(params, opt_state)
        if restored is not None:
            params, opt_state, start_step = restored

    first = next(data_iter)
    step_fn = jit_train_step(mesh, cfg, hp, params, opt_state)(first)
    metrics_hist = []
    batch = first
    t0 = time.time()
    for step in range(start_step, steps):
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        if (step + 1) % log_every == 0:
            m = {k: float(v) for k, v in metrics.items()}
            m["step"] = step + 1
            m["sec_per_step"] = (time.time() - t0) / log_every
            t0 = time.time()
            metrics_hist.append(m)
            print(
                f"step {m['step']:6d} loss={m['loss']:.4f} "
                f"gnorm={m['grad_norm']:.3f} lr={m['lr']:.2e} "
                f"{m['sec_per_step']:.3f}s/step",
                flush=True,
            )
        if checkpoint_manager is not None and (step + 1) % checkpoint_every == 0:
            checkpoint_manager.save(step + 1, params, opt_state)
        try:
            batch = next(data_iter)
        except StopIteration:
            break
    return params, opt_state, metrics_hist
