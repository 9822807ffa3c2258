"""Trainer: the per-device SPMD train step (loss -> grads -> DP reduction ->
AdamW/ZeRO-1 update) with microbatched gradient accumulation, remat, and
optional gradient compression.

The same step function serves three consumers:
  * launch/train.py      — real execution on a small mesh
  * launch/dryrun.py     — .lower().compile() on the 512-device mesh
  * repro.core verifier  — single-device vs per-device graph equivalence
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.models import Model

from .compression import allreduce_compressed
from .optimizer import AdamWConfig, adamw_update, zero1_update


@dataclass(frozen=True)
class TrainConfig:
    opt: AdamWConfig = AdamWConfig()
    microbatches: int = 1
    remat: bool = True
    zero1: bool = False
    grad_compress: str = "none"  # none | bf16 | int8
    unroll_layers: bool = False


def _split_micro(batch, n: int):
    def f(x):
        b = x.shape[0]
        return x.reshape(n, b // n, *x.shape[1:])

    return jax.tree_util.tree_map(f, batch)


def local_grads(model: Model, tcfg: TrainConfig, params, batch):
    """Per-device loss + grads with microbatch accumulation (no DP reduction)."""
    loss_of = lambda p, b: model.loss(p, b, remat=tcfg.remat, unroll=tcfg.unroll_layers)
    if tcfg.microbatches <= 1:
        with jax.named_scope("microbatch"):
            loss, grads = jax.value_and_grad(loss_of)(params, batch)
        return loss, grads
    micro = _split_micro(batch, tcfg.microbatches)

    def body(carry, mb):
        acc_loss, acc_g = carry
        with jax.named_scope("microbatch"):
            loss, g = jax.value_and_grad(loss_of)(params, mb)
        acc_g = jax.tree_util.tree_map(jnp.add, acc_g, g)
        return (acc_loss + loss, acc_g), None

    zero_g = jax.tree_util.tree_map(lambda p: jnp.zeros(p.shape, p.dtype), params)
    (loss_sum, gsum), _ = lax.scan(body, (jnp.zeros((), jnp.float32), zero_g), micro)
    inv = 1.0 / tcfg.microbatches
    grads = jax.tree_util.tree_map(lambda g: g * inv, gsum)
    return loss_sum * inv, grads


def tp_sharded_leaves(params, tp_axis: str):
    """Pytree of bools: which leaves ``param_specs`` shards over ``tp_axis``."""
    from repro.parallel.sharding import param_specs

    return jax.tree_util.tree_map(lambda s: tp_axis in tuple(s),
                                  param_specs(params, tp_axis),
                                  is_leaf=lambda s: isinstance(s, P))


def tp_reduce_grads(grads, tp_sharded, tp_axis: str, tp_size: int):
    """The gradients of the loss from those shard_map gives without
    replication checks (``check_vma=False``), where every rank's equal loss
    counts as an output of its own: they are the gradients of the sum of the
    ``tp_size`` losses, and a replicated leaf's copy holds only what its own
    rank's work contributes.  Divide by ``tp_size``, and sum the copies of a
    replicated leaf over ``tp_axis``."""
    return jax.tree_util.tree_map(
        lambda g, f: (g if f else lax.psum(g, tp_axis)) / tp_size, grads, tp_sharded)


def make_step_fn(model: Model, tcfg: TrainConfig, shard_flags=None):
    """The per-device train step (to be wrapped in shard_map by the caller,
    with ``check_vma=False``).

    signature: (params, opt_state, batch) -> (params, opt_state, metrics)
    """
    ctx = model.ctx
    tp = ctx.tp_axis if ctx.tp_axis and ctx.tp_size > 1 else None

    def step(params, opt_state, batch):
        loss, grads = local_grads(model, tcfg, params, batch)
        split = tp_sharded_leaves(params, tp) if tp else None
        if tp:
            grads = tp_reduce_grads(grads, split, tp, ctx.tp_size)
        if ctx.dp_axis:
            loss = lax.pmean(loss, ctx.dp_axis)
        if tcfg.zero1 and ctx.dp_axis:
            axes = ctx.dp_axis if isinstance(ctx.dp_axis, tuple) else (ctx.dp_axis,)
            sizes = ctx.dp_axis_sizes or (ctx.dp_size,)
            scatter_axis, others = axes[-1], axes[:-1]
            if others:
                grads = jax.tree_util.tree_map(lambda g: lax.psum(g, others), grads)
            with jax.named_scope("optimizer"):
                new_p, new_s, info = zero1_update(
                    tcfg.opt, params, grads, opt_state, scatter_axis, sizes[-1],
                    shard_flags, tp_sharded=split, tp_axis=tp)
        else:
            if ctx.dp_axis:
                grads = allreduce_compressed(grads, tcfg.grad_compress, ctx.dp_axis)
                grads = jax.tree_util.tree_map(lambda g: g / ctx.dp_size, grads)
            with jax.named_scope("optimizer"):
                new_p, new_s, info = adamw_update(tcfg.opt, params, grads,
                                                  opt_state, tp_sharded=split,
                                                  tp_axis=tp)
        metrics = {"loss": loss, **info}
        return new_p, new_s, metrics

    return step
