"""Pallas TPU flash attention (forward) with GQA and causal masking.

Tiling: grid (B, Hq, Sq/BQ, Sk/BK); the KV dim is innermost so each (b, h, iq)
row accumulates online-softmax state across KV blocks in VMEM scratch.  GQA
maps query head h to KV head h // G in the k/v index_maps — no repeated KV in
HBM.

Tiles come from the shape (``block_sizes``): per sequence dim, the largest of
512/256/128 rows that pads the length no further than 128-row alignment does
(a length under 128 is one block, so decode runs BQ = 1).  A 512-square tile
does 16x the work of a 128-square one for the same per-grid-step cost.

Causal schedule: query block iq attends to KV blocks 0..``last_kv_block(iq)``,
the last one holding a key position at or before the block's last query
position.  The blocks after it run no matmul and no exp, and their k/v
index_maps are clamped to that last block, so Pallas issues no copy for them.
``visited_blocks`` counts the (iq, ik) pairs that run from the same function.
The iota/compare mask is built only on blocks that straddle the diagonal or
hold the padded ``kv_len`` tail; interior blocks take their scores unmasked.

On CPU this runs with interpret=True and is validated against
ref.attention_ref and the chunked jnp implementation (three-way).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
TILES = (512, 256, 128)  # rows a block may take; a (512, 512) f32 score tile is 1 MiB
LANES = 128  # m and l are kept lane-replicated, (BQ, 128), as the scores' rows broadcast


def _tile(s: int) -> int:
    """Rows of one block along a sequence dim of length ``s``."""
    if s <= TILES[-1]:
        return s
    aligned = -(-s // TILES[-1]) * TILES[-1]
    return next(t for t in TILES if t <= s and aligned % t == 0)


def block_sizes(sq: int, sk: int) -> tuple[int, int]:
    """(BQ, BK) for query and key lengths ``sq`` and ``sk``."""
    return _tile(sq), _tile(sk)


def last_kv_block(iq, bq: int, bk: int, n_k: int, causal: bool):
    """Index of the last KV block that query block ``iq`` attends to: the one
    holding the key position of the block's last query row (positions from 0),
    or every block without ``causal``.  Takes a traced or a NumPy ``iq``."""
    if not causal:
        return n_k - 1
    return jnp.minimum((iq * bq + bq - 1) // bk, n_k - 1)


def visited_blocks(sq: int, sk: int, bq: int, bk: int,
                   causal: bool) -> tuple[int, int]:
    """(visited, total) (iq, ik) block pairs of one (b, h) row of the grid."""
    n_q, n_k = -(-sq // bq), -(-sk // bk)
    last = np.broadcast_to(last_kv_block(np.arange(n_q), bq, bk, n_k, causal), n_q)
    return int(np.sum(last + 1)), n_q * n_k


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, bq: int, bk: int, n_k: int,
                  kv_len: int):
    ik = pl.program_id(3)
    iq = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def step(masked: bool):
        q = q_ref[0, 0]  # (BQ, D)
        k = k_ref[0, 0]  # (BK, D)
        v = v_ref[0, 0]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (BQ, BK)
        if masked:
            q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            mask = k_pos < kv_len
            if causal:
                mask &= q_pos >= k_pos
            s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]  # (BQ, LANES), every lane the same
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new[:, :1])
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr[:, :1] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        l_ref[...] = l_new

    # the mask is needed where a key lies past kv_len (the padded tail of the
    # last block) or, causal, past the block's first query row
    needs_mask = ik * bk + bk > kv_len
    if causal:
        needs_mask |= ik * bk + bk - 1 > iq * bq
    runs = ik <= last_kv_block(iq, bq, bk, n_k, causal)
    pl.when(runs & needs_mask)(functools.partial(step, True))
    pl.when(runs & ~needs_mask)(functools.partial(step, False))

    @pl.when(ik == n_k - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[:, :1], 1e-30)).astype(
            o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, block_q: int | None = None,
                    block_k: int | None = None, interpret: bool = False,
                    scale: float | None = None):
    """q: (B, Hq, Sq, D); k/v: (B, Hkv, Sk, D) -> (B, Hq, Sq, D); the
    softmax scale of q·k is ``scale`` (default 1/sqrt(D)).  ``block_q`` and
    ``block_k`` default to ``block_sizes(Sq, Sk)``."""
    B, Hq, Sq, D = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    G = Hq // Hkv
    auto_q, auto_k = block_sizes(Sq, Sk)
    bq = min(block_q or auto_q, Sq)
    bk = min(block_k or auto_k, Sk)
    # pad sequence dims to block multiples (out-of-range keys are masked by
    # kv_len; padded query rows are sliced off the output)
    pad_q = (-Sq) % bq
    pad_k = (-Sk) % bk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    n_q = (Sq + pad_q) // bq
    n_k = (Sk + pad_k) // bk
    scale = 1.0 / math.sqrt(D) if scale is None else scale

    def kv_index(b, h, iq, ik):
        if causal:  # past the row's last block: stay on it, so nothing is copied
            ik = jnp.minimum(ik, last_kv_block(iq, bq, bk, n_k, causal))
        return b, h // G, ik, 0

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, bq=bq, bk=bk, n_k=n_k,
        kv_len=Sk)
    out = pl.pallas_call(
        kernel,
        grid=(B, Hq, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, D), kv_index),
            pl.BlockSpec((1, 1, bk, D), kv_index),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, LANES), jnp.float32),  # running max m
            pltpu.VMEM((bq, LANES), jnp.float32),  # running denom l
            pltpu.VMEM((bq, D), jnp.float32),      # weighted accumulator
        ],
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :Sq] if pad_q else out
