"""AdamW with warmup-cosine schedule, gradient clipping, and optional ZeRO-1
optimizer-state sharding over the data axis.

ZeRO-1 (beyond-paper §Perf optimization): optimizer moments are sharded over
dp; each rank updates its shard of the flattened parameter and the updated
shard is re-gathered.  On a leaf level we shard the *leading dim* of every
moment tensor over dp when divisible, falling back to replication otherwise —
simple, deterministic, and enough to cut optimizer memory by ~dp_size.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 10_000
    min_lr_frac: float = 0.1


def lr_schedule(cfg: AdamWConfig, step):
    step = jnp.asarray(step, jnp.float32)
    warm = jnp.minimum(step / jnp.maximum(cfg.warmup_steps, 1), 1.0)
    prog = jnp.clip(
        (step - cfg.warmup_steps) / jnp.maximum(cfg.total_steps - cfg.warmup_steps, 1),
        0.0,
        1.0,
    )
    cos = 0.5 * (1 + jnp.cos(jnp.pi * prog))
    return cfg.lr * warm * (cfg.min_lr_frac + (1 - cfg.min_lr_frac) * cos)


def adamw_init(params) -> dict:
    zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
    return {
        "m": jax.tree_util.tree_map(zeros, params),
        "v": jax.tree_util.tree_map(zeros, params),
        "step": jnp.zeros((), jnp.int32),
    }


def _sum_sq(sq: list, tp_sharded=None, tp_axis=None):
    """The sum of per-leaf squared norms.  Inside shard_map, ``tp_sharded``
    (a pytree of bools) marks the leaves sharded over ``tp_axis``: their
    local parts are summed over it, the replicated leaves counted once."""
    if tp_axis is None:
        return sum(sq)
    flags = jax.tree_util.tree_leaves(tp_sharded)
    split = sum((x for x, f in zip(sq, flags) if f), jnp.float32(0.0))
    rest = sum((x for x, f in zip(sq, flags) if not f), jnp.float32(0.0))
    return jax.lax.psum(split, tp_axis) + rest


def global_norm(tree, tp_sharded=None, tp_axis=None) -> jnp.ndarray:
    """The gradient's norm (``_sum_sq`` for the TP arguments)."""
    sq = [jnp.sum(jnp.square(leaf.astype(jnp.float32)))
          for leaf in jax.tree_util.tree_leaves(tree)]
    return jnp.sqrt(_sum_sq(sq, tp_sharded, tp_axis))


def adamw_update(cfg: AdamWConfig, params, grads, state, *, tp_sharded=None,
                 tp_axis=None):
    """One AdamW step.  Grads are assumed already averaged across DP; under
    tensor parallelism ``tp_sharded``/``tp_axis`` give the norm's shards
    (``_sum_sq``).

    Fault tolerance: a non-finite gradient norm (overflow/NaN from a bad
    batch or a flipped bit) zeroes the update for the whole step instead of
    corrupting parameters — the standard skip-step guard."""
    step = state["step"] + 1
    lr = lr_schedule(cfg, step)
    gnorm = global_norm(grads, tp_sharded, tp_axis)
    ok = jnp.isfinite(gnorm)  # skip-step guard: NaN/inf grads leave state as-is
    scale = jnp.minimum(1.0, cfg.grad_clip / jnp.maximum(gnorm, 1e-12))

    b1, b2 = cfg.b1, cfg.b2
    corr1 = 1 - b1 ** step.astype(jnp.float32)
    corr2 = 1 - b2 ** step.astype(jnp.float32)

    def upd(p, g, m, v):
        g = jnp.where(jnp.isfinite(g), g.astype(jnp.float32), 0.0) * scale
        m_new = b1 * m + (1 - b1) * g
        v_new = b2 * v + (1 - b2) * g * g
        mhat = m_new / corr1
        vhat = v_new / corr2
        delta = mhat / (jnp.sqrt(vhat) + cfg.eps) + cfg.weight_decay * p.astype(jnp.float32)
        p_new = (p.astype(jnp.float32) - lr * delta).astype(p.dtype)
        return (jnp.where(ok, p_new, p),
                jnp.where(ok, m_new, m),
                jnp.where(ok, v_new, v))

    flat_p, treedef = jax.tree_util.tree_flatten(params)
    flat_g = jax.tree_util.tree_leaves(grads)
    flat_m = jax.tree_util.tree_leaves(state["m"])
    flat_v = jax.tree_util.tree_leaves(state["v"])
    out = [upd(p, g, m, v) for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v)]
    new_p = jax.tree_util.tree_unflatten(treedef, [o[0] for o in out])
    new_m = jax.tree_util.tree_unflatten(treedef, [o[1] for o in out])
    new_v = jax.tree_util.tree_unflatten(treedef, [o[2] for o in out])
    return new_p, {"m": new_m, "v": new_v, "step": step}, {"grad_norm": gnorm, "lr": lr}


# ---------------------------------------------------------------------------
# ZeRO-1: shard optimizer moments (and the f32 update math) over dp


def _shardable(shape, dp: int) -> bool:
    return len(shape) > 0 and shape[0] % dp == 0


def zero1_init(params, dp: int) -> dict:
    """Optimizer moments holding only this rank's 1/dp slice (leading dim)."""

    def zeros(p):
        if _shardable(p.shape, dp):
            return jnp.zeros((p.shape[0] // dp, *p.shape[1:]), jnp.float32)
        return jnp.zeros(p.shape, jnp.float32)

    return {
        "m": jax.tree_util.tree_map(zeros, params),
        "v": jax.tree_util.tree_map(zeros, params),
        "step": jnp.zeros((), jnp.int32),
    }


def zero1_shard_dim(shape, dp: int, blocked_dims=()) -> int:
    """First dim divisible by dp (excluding blocked dims), or -1."""
    for i, s in enumerate(shape):
        if i not in blocked_dims and s % dp == 0 and s >= dp:
            return i
    return -1


def zero1_shard_flags(params, dp: int):
    """Per-leaf shard dim for ZeRO-1 moments (pytree of int; -1 = replicated)."""
    return jax.tree_util.tree_map(lambda p: zero1_shard_dim(p.shape, dp), params)


def zero1_update(cfg: AdamWConfig, params, grads, state, dp_axis, dp: int,
                 shard_flags=None, tp_sharded=None, tp_axis=None):
    """ZeRO-1 step inside shard_map: reduce_scatter grads over dp, update the
    local parameter shard, all_gather updated shards.  ``shard_flags`` is a
    pytree of shard dims per leaf (-1 = replicated moments); ``tp_sharded``
    and ``tp_axis`` as for ``_sum_sq``."""
    step = state["step"] + 1
    lr = lr_schedule(cfg, step)
    b1, b2 = cfg.b1, cfg.b2
    corr1 = 1 - b1 ** step.astype(jnp.float32)
    corr2 = 1 - b2 ** step.astype(jnp.float32)

    flat_p, treedef = jax.tree_util.tree_flatten(params)
    flat_g = jax.tree_util.tree_leaves(grads)
    flat_m = jax.tree_util.tree_leaves(state["m"])
    flat_v = jax.tree_util.tree_leaves(state["v"])
    if shard_flags is None:
        flat_dims = [zero1_shard_dim(p.shape, dp) for p in flat_p]
    else:
        flat_dims = [
            (0 if f is True else -1 if f is False else int(f))
            for f in jax.tree_util.tree_leaves(shard_flags)
        ]

    # pass 1: average + shard the grads (reduce_scatter replaces all_reduce)
    gsh_all = []
    for p, g, dim in zip(flat_p, flat_g, flat_dims):
        if dim >= 0:
            gsh = jax.lax.psum_scatter(g.astype(jnp.float32), dp_axis,
                                       scatter_dimension=dim, tiled=True) / dp
        else:
            gsh = jax.lax.psum(g.astype(jnp.float32), dp_axis) / dp
        gsh_all.append(gsh)

    # global grad norm from the scattered shards (replicated leaves counted once)
    sq = [jnp.sum(jnp.square(g)) if dim >= 0 else jnp.sum(jnp.square(g)) / dp
          for g, dim in zip(gsh_all, flat_dims)]
    gnorm = jnp.sqrt(jax.lax.psum(_sum_sq(sq, tp_sharded, tp_axis), dp_axis))
    scale = jnp.minimum(1.0, cfg.grad_clip / jnp.maximum(gnorm, 1e-12))
    scale = jnp.where(jnp.isfinite(gnorm), scale, 0.0)  # skip-step guard

    # pass 2: AdamW on the local shard, then re-gather parameters
    out = []
    for p, gsh, m, v, dim in zip(flat_p, gsh_all, flat_m, flat_v, flat_dims):
        if dim >= 0:
            chunk = p.shape[dim] // dp
            psh = jax.lax.dynamic_slice_in_dim(
                p, jax.lax.axis_index(dp_axis) * chunk, chunk, dim
            ).astype(jnp.float32)
        else:
            psh = p.astype(jnp.float32)
        g = gsh * scale
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        delta = (m / corr1) / (jnp.sqrt(v / corr2) + cfg.eps) + cfg.weight_decay * psh
        new_psh = (psh - lr * delta).astype(p.dtype)
        new_p = (
            jax.lax.all_gather(new_psh, dp_axis, axis=dim, tiled=True)
            if dim >= 0 else new_psh
        )
        out.append((new_p, m, v))

    new_p = jax.tree_util.tree_unflatten(treedef, [o[0] for o in out])
    new_m = jax.tree_util.tree_unflatten(treedef, [o[1] for o in out])
    new_v = jax.tree_util.tree_unflatten(treedef, [o[2] for o in out])
    return new_p, {"m": new_m, "v": new_v, "step": step}, {"grad_norm": gnorm, "lr": lr}
