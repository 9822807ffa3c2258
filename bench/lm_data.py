"""Synthetic token batches made on the device from a seed.

A mixture of first-order Markov chains, as ``repro.data.SyntheticLM`` makes
it on the host: each of ``n_states`` states prefers ``prefs`` tokens, a
token's successor is drawn from the preferences of the token's state, and
with probability ``explore`` from the whole vocabulary.  Every row of every
batch is its own draw, so no two rows repeat.  Made in one jitted call.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int, stream: int):
    """A raw threefry key from any non-negative integer seed and a stream
    number (weights, data, ...)."""
    words = np.random.SeedSequence([seed, stream]).generate_state(2, np.uint32)
    return jnp.asarray(words, jnp.uint32)


@partial(jax.jit, static_argnames=("vocab", "seq", "batch", "n", "n_states",
                                   "prefs", "explore"))
def batches(key, *, vocab: int, seq: int, batch: int, n: int,
            n_states: int = 64, prefs: int = 8, explore: float = 0.1):
    """``n`` batches: tokens and next-token labels, each (n, batch, seq)."""
    k_tab, k_state, k_first, k_walk = jax.random.split(key, 4)
    table = jax.random.randint(k_tab, (n_states, prefs), 0, vocab)
    state_of = jax.random.randint(k_state, (vocab,), 0, n_states)
    rows = n * batch
    ka, kb = jax.random.split(k_first)
    first = table[jax.random.randint(ka, (rows,), 0, n_states),
                  jax.random.randint(kb, (rows,), 0, prefs)]

    def step(tok, k):
        kc, ke, kv = jax.random.split(k, 3)
        nxt = table[state_of[tok], jax.random.randint(kc, (rows,), 0, prefs)]
        wild = jax.random.randint(kv, (rows,), 0, vocab)
        nxt = jnp.where(jax.random.uniform(ke, (rows,)) < explore, wild, nxt)
        return nxt, nxt

    _, rest = jax.lax.scan(step, first, jax.random.split(k_walk, seq))
    toks = jnp.concatenate([first[None], rest], 0).T.reshape(n, batch, seq + 1)
    return toks[..., :-1].astype(jnp.int32), toks[..., 1:].astype(jnp.int32)
