"""AdamW written out plainly, for the reference side of a training check.

Loshchilov & Hutter (arXiv:1711.05101): moments with bias correction and
decoupled weight decay; the gradient clipped to a global norm first; the
learning rate warmed up linearly and then decayed along a cosine to
``min_lr_frac`` of its peak.  The hyperparameters are the cell's, named as
its traffic file names them.  Parameters are rounded to the type they are
stored in after each update, as the configuration stores them.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp


def init(params) -> dict:
    zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
    return {"m": jax.tree_util.tree_map(zeros, params),
            "v": jax.tree_util.tree_map(zeros, params), "t": 0}


def lr_at(cfg, t: int) -> float:
    warm = min(t / max(cfg.warmup_steps, 1), 1.0)
    prog = min(max((t - cfg.warmup_steps)
                   / max(cfg.total_steps - cfg.warmup_steps, 1), 0.0), 1.0)
    cos = 0.5 * (1 + math.cos(math.pi * prog))
    return cfg.lr * warm * (cfg.min_lr_frac + (1 - cfg.min_lr_frac) * cos)


def step(cfg, params, grads, state, dtypes: tuple):
    """One update; returns (params, state, the clipped gradient).
    ``dtypes`` names the stored type of each leaf, in leaf order."""
    t = state["t"] + 1
    hyper = (lr_at(cfg, t), 1 - cfg.b1 ** t, 1 - cfg.b2 ** t)
    params, m, v, g = _update(cfg, dtypes, params, grads, state["m"], state["v"],
                              *map(jnp.float32, hyper))
    return params, {"m": m, "v": v, "t": t}, g


@partial(jax.jit, static_argnums=(0, 1))
def _update(cfg, dtypes, params, grads, m, v, lr, c1, c2):
    leaves = jax.tree_util.tree_leaves(grads)
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in leaves))
    clip = jnp.minimum(1.0, cfg.grad_clip / jnp.maximum(norm, 1e-12))
    g = jax.tree_util.tree_map(lambda x: x * clip, grads)
    m = jax.tree_util.tree_map(lambda m, x: cfg.b1 * m + (1 - cfg.b1) * x, m, g)
    v = jax.tree_util.tree_map(lambda v, x: cfg.b2 * v + (1 - cfg.b2) * x * x, v, g)

    def upd(p, m, v, dt):
        new = p - lr * ((m / c1) / (jnp.sqrt(v / c2) + cfg.eps) + cfg.weight_decay * p)
        if dt == "float32":
            return new
        # rounded by an op XLA keeps: with excess precision allowed (its
        # default), a round trip through astype may be folded away
        info = jnp.finfo(dt)
        return jax.lax.reduce_precision(new, exponent_bits=info.nexp,
                                        mantissa_bits=info.nmant)

    flat, tdef = jax.tree_util.tree_flatten(params)
    leaves = lambda t: jax.tree_util.tree_leaves(t)
    new = [upd(*a) for a in zip(flat, leaves(m), leaves(v), dtypes)]
    return jax.tree_util.tree_unflatten(tdef, new), m, v, g
