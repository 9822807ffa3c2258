"""Pallas TPU flash attention (forward) with GQA and causal masking.

Tiling: grid (B, Hq, Sq/BQ, Sk/BK); the KV dim is innermost so each (b, h, iq)
row accumulates online-softmax state across KV blocks in VMEM scratch.
Block shapes are MXU-aligned (BQ=BK=128 sublane x lane tiles; head_dim is the
lane dim of the QK^T contraction).  GQA maps query head h to KV head h // G
in the k/v index_maps — no repeated KV in HBM.

On CPU this runs with interpret=True and is validated against
ref.attention_ref and the chunked jnp implementation (three-way).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


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

    q = q_ref[0, 0]  # (BQ, D)
    k = k_ref[0, 0]  # (BK, D)
    v = v_ref[0, 0]

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # (BQ, BK)

    q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = k_pos < kv_len
    if causal:
        mask &= q_pos >= k_pos
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    l_prev = l_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + p.sum(axis=-1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(ik == n_k - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[:, None]).astype(
            o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False,
                    scale: float | None = None):
    """q: (B, Hq, Sq, D); k/v: (B, Hkv, Sk, D) -> (B, Hq, Sq, D); the
    softmax scale of q·k is ``scale`` (default 1/sqrt(D))."""
    B, Hq, Sq, D = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    G = Hq // Hkv
    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
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

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, bq=bq, bk=bk, n_k=n_k,
        kv_len=Sk)
    out = pl.pallas_call(
        kernel,
        grid=(B, Hq, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, iq, ik: (b, h // G, ik, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, iq, ik: (b, h // G, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),      # running max m
            pltpu.VMEM((bq,), jnp.float32),      # running denom l
            pltpu.VMEM((bq, D), jnp.float32),    # weighted accumulator
        ],
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :Sq] if pad_q else out
