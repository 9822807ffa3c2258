"""GQA/MQA/MHA attention with RoPE variants, qk-norm, biases, KV caches, and
chunked (flash-style online-softmax) computation.

The chunked jnp implementation is the semantic reference; on TPU the Pallas
flash-attention kernel (repro.kernels.flash_attention) swaps in via
``impl="pallas"``.  Both are numerically cross-checked in tests/.

Context-parallel flash decoding (long_500k): the KV cache is sharded along
the sequence dim over ``ctx.cp_axis``; each device computes a partial
(max, sum, acc) triple and the results merge with pmax/psum — the same
flash-decoding pattern the paper verifies (§7.1).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.parallel.ctx import ParallelCtx

from .modules import apply_rope, linear, linear_init, rmsnorm, rmsnorm_init


def attn_init(key, cfg, *, stacked: tuple = (), dtype=jnp.bfloat16):
    hd = cfg.hd
    ks = jax.random.split(key, 6)
    p = {
        "wq": linear_init(ks[0], cfg.d_model, cfg.heads * hd, bias=cfg.qkv_bias,
                          dtype=dtype, stacked=stacked),
        "wk": linear_init(ks[1], cfg.d_model, cfg.kv_heads * hd, bias=cfg.qkv_bias,
                          dtype=dtype, stacked=stacked),
        "wv": linear_init(ks[2], cfg.d_model, cfg.kv_heads * hd, bias=cfg.qkv_bias,
                          dtype=dtype, stacked=stacked),
        "wo": linear_init(ks[3], cfg.heads * hd, cfg.d_model, dtype=dtype, stacked=stacked),
    }
    if cfg.qk_norm:
        p["qnorm"] = rmsnorm_init(ks[4], hd, dtype, stacked)
        p["knorm"] = rmsnorm_init(ks[5], hd, dtype, stacked)
    return p


def _split_heads(x, n_heads: int):
    B, S, _ = x.shape
    return x.reshape(B, S, n_heads, -1).transpose(0, 2, 1, 3)  # (B,H,S,hd)


def chunked_attention(
    q, k, v, *, causal: bool, q_offset=0, k_offset=0, kv_len: Optional[jnp.ndarray] = None,
    chunk: int = 1024, with_stats: bool = False, unroll: bool = False,
    scale: Optional[float] = None,
):
    """Flash-style online-softmax attention in pure jnp.

    q: (B, Hq, Sq, hd); k/v: (B, Hkv, Sk, hd) with Hq = G * Hkv.
    ``kv_len``: optional dynamic valid length (decode masking).
    ``with_stats``: also return (m, l) running stats for cross-device merges.
    ``scale``: softmax scale of q·k (default 1/sqrt(hd)).
    """
    B, Hq, Sq, hd = q.shape
    _, Hkv, Sk, _ = k.shape
    G = Hq // Hkv
    qg = q.reshape(B, Hkv, G, Sq, hd)
    scale = 1.0 / math.sqrt(hd) if scale is None else scale
    chunk = min(chunk, Sk)
    n_chunks = (Sk + chunk - 1) // chunk
    pad = n_chunks * chunk - Sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    kc = k.reshape(B, Hkv, n_chunks, chunk, hd).transpose(2, 0, 1, 3, 4)
    vc = v.reshape(B, Hkv, n_chunks, chunk, hd).transpose(2, 0, 1, 3, 4)
    q_pos = q_offset + jnp.arange(Sq)

    scope = jax.named_scope("flash_attn")
    scope.__enter__()
    neg = jnp.float32(-1e30)
    m0 = jnp.full((B, Hkv, G, Sq), neg, jnp.float32)
    l0 = jnp.zeros((B, Hkv, G, Sq), jnp.float32)
    a0 = jnp.zeros((B, Hkv, G, Sq, hd), jnp.float32)

    def body(carry, blk):
        m, denom, acc = carry
        kb, vb, ci = blk
        s = jnp.einsum("bhgqd,bhkd->bhgqk", qg, kb,
                       preferred_element_type=jnp.float32) * scale
        k_pos = k_offset + ci * chunk + jnp.arange(chunk)
        mask = jnp.ones((Sq, chunk), bool)
        if causal:
            mask &= q_pos[:, None] >= k_pos[None, :]
        if kv_len is not None:
            mask &= k_pos[None, :] < kv_len
        if pad:
            mask &= (ci * chunk + jnp.arange(chunk) < Sk)[None, :]
        s = jnp.where(mask[None, None, None], s, neg)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        denom_new = denom * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhgqk,bhkd->bhgqd", p.astype(vb.dtype), vb,
            preferred_element_type=jnp.float32)
        return (m_new, denom_new, acc_new), None

    if unroll:  # verification traces: no scan nodes (paper-style unrolled IR)
        carry = (m0, l0, a0)
        for ci in range(n_chunks):
            carry, _ = body(carry, (kc[ci], vc[ci], jnp.int32(ci)))
        m, denom, acc = carry
    else:
        (m, denom, acc), _ = lax.scan(body, (m0, l0, a0), (kc, vc, jnp.arange(n_chunks)))
    if with_stats:
        scope.__exit__(None, None, None)
        return acc, m, denom
    out = acc / jnp.maximum(denom[..., None], 1e-30)
    out = out.reshape(B, Hq, Sq, hd).astype(q.dtype)
    scope.__exit__(None, None, None)
    return out


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_pallas(q, k, v, causal: bool, scale: float):
    """Attention through the Pallas flash kernel (Mosaic; TPU only).  The
    backward pass is the vjp of ``chunked_attention``, recomputed from the
    saved inputs."""
    # imported here: loading Pallas adds seconds to every cold verify
    from repro.kernels import ops as kops

    return kops.flash_attention(q, k, v, causal=causal, scale=scale)


def _flash_pallas_fwd(q, k, v, causal, scale):
    return flash_pallas(q, k, v, causal, scale), (q, k, v)


def _flash_pallas_bwd(causal, scale, res, g):
    _, vjp = jax.vjp(lambda *a: chunked_attention(*a, causal=causal, scale=scale),
                     *res)
    return vjp(g)


flash_pallas.defvjp(_flash_pallas_fwd, _flash_pallas_bwd)


def attn_fwd(cfg, ctx: ParallelCtx, p, x, positions, *, impl: str = "reference",
             unroll: bool = False):
    """Full-sequence attention (train / prefill).  x: (B, S, D) replicated
    (caller handles SP enter/exit)."""
    B, S, _ = x.shape
    hd = cfg.hd
    q = linear(p["wq"], x)
    k = linear(p["wk"], x)
    v = linear(p["wv"], x)
    Hq_loc = q.shape[-1] // hd
    Hkv_loc = k.shape[-1] // hd
    q = _split_heads(q, Hq_loc)
    k = _split_heads(k, Hkv_loc)
    v = _split_heads(v, Hkv_loc)
    if cfg.qk_norm:
        q = rmsnorm(p["qnorm"], q, cfg.norm_eps)
        k = rmsnorm(p["knorm"], k, cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_fraction, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_fraction, cfg.rope_theta)
    if impl == "pallas":
        out = flash_pallas(q, k, v, cfg.causal, cfg.attn_scale)
    else:
        out = chunked_attention(q, k, v, causal=cfg.causal, unroll=unroll,
                                scale=cfg.attn_scale)
    out = out.transpose(0, 2, 1, 3).reshape(B, S, Hq_loc * hd)
    y = linear(p["wo"], out)  # row-parallel -> partial sum across tp
    return ctx.sp_enter(y)


def attn_init_cache(cfg, batch: int, max_len: int, tp_size: int = 1, cp_size: int = 1,
                    dtype=jnp.bfloat16):
    """Per-layer KV cache buffers.  Under context parallelism the sequence dim
    is the per-device shard (max_len // cp_size handled by the caller)."""
    hd = cfg.hd
    kv = cfg.kv_heads // tp_size
    shape = (batch, kv, max_len, hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def attn_decode(cfg, ctx: ParallelCtx, p, x, cache, position, *, unroll: bool = False):
    """Single-token decode with KV cache update.

    x: (B, 1, D).  cache k/v: (B, Hkv_loc, S_loc, hd); with context parallelism
    S_loc = S_global / cp and the new token is written on the owning shard.
    """
    B = x.shape[0]
    hd = cfg.hd
    q = linear(p["wq"], x)
    k = linear(p["wk"], x)
    v = linear(p["wv"], x)
    Hq_loc = q.shape[-1] // hd
    Hkv_loc = k.shape[-1] // hd
    q = _split_heads(q, Hq_loc)
    knew = _split_heads(k, Hkv_loc)
    vnew = _split_heads(v, Hkv_loc)
    if cfg.qk_norm:
        q = rmsnorm(p["qnorm"], q, cfg.norm_eps)
        knew = rmsnorm(p["knorm"], knew, cfg.norm_eps)
    q = apply_rope(q, position[None] if position.ndim == 0 else position,
                   cfg.rope_fraction, cfg.rope_theta)
    knew = apply_rope(knew, position[None] if position.ndim == 0 else position,
                      cfg.rope_fraction, cfg.rope_theta)

    S_loc = cache["k"].shape[2]
    if ctx.cp_axis:  # context parallel: only the owning shard stores the token
        shard = ctx.cp_index()
        local_pos = position - shard * S_loc
        in_range = (local_pos >= 0) & (local_pos < S_loc)
        write_pos = jnp.clip(local_pos, 0, S_loc - 1)
        old_k = lax.dynamic_slice_in_dim(cache["k"], write_pos, 1, axis=2)
        old_v = lax.dynamic_slice_in_dim(cache["v"], write_pos, 1, axis=2)
        k_upd = jnp.where(in_range, knew, old_k)
        v_upd = jnp.where(in_range, vnew, old_v)
        new_k = lax.dynamic_update_slice_in_dim(cache["k"], k_upd, write_pos, axis=2)
        new_v = lax.dynamic_update_slice_in_dim(cache["v"], v_upd, write_pos, axis=2)
        k_off = shard * S_loc
        kv_len = position + 1
        acc, m, denom = chunked_attention(
            q, new_k, new_v, causal=False, q_offset=0, k_offset=k_off,
            kv_len=kv_len, with_stats=True, unroll=unroll, scale=cfg.attn_scale)
        # flash-decode merge across shards (verified pattern, paper §7.1)
        m_g = ctx.pmax_cp(m)
        corr = jnp.exp(m - m_g)
        l_g = ctx.psum_cp(denom * corr)
        acc_g = ctx.psum_cp(acc * corr[..., None])
        out = acc_g / jnp.maximum(l_g[..., None], 1e-30)
        out = out.reshape(B, Hq_loc, 1, hd).astype(q.dtype)
    else:
        new_k = lax.dynamic_update_slice_in_dim(cache["k"], knew, position, axis=2)
        new_v = lax.dynamic_update_slice_in_dim(cache["v"], vnew, position, axis=2)
        out = chunked_attention(q, new_k, new_v, causal=False, kv_len=position + 1,
                                unroll=unroll, scale=cfg.attn_scale)
    out = out.transpose(0, 2, 1, 3).reshape(B, 1, Hq_loc * hd)
    y = linear(p["wo"], out)
    return ctx.sp_enter(y), {"k": new_k, "v": new_v}
