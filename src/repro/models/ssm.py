"""Mamba-2 SSD (state-space duality) block [arXiv:2405.21060].

Chunked SSD algorithm in pure jnp (the reference; the Pallas kernel in
repro.kernels.ssd_scan mirrors the chunk-parallel structure on TPU):

  within chunk:  Y_diag = (C B^T ⊙ L) · (dt x)        (attention-like matmuls)
  chunk states:  S_c    = Σ_k decay_to_end · dt_k B_k x_k^T
  across chunks: S_c   <- S_{c-1} · Π decay + S_c      (one decay-matrix
                 contraction over chunks, no loop; written out for verification)
  offset:        Y_off  = decay_from_start · C S_{c-1}

TP shards the SSD heads over ``model``; B/C projections are replicated
(single-group SSD), so all per-head compute is rank-local and only the
output row-projection needs a psum.  Decode keeps O(1) state per head.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from repro.parallel.ctx import ParallelCtx

from .modules import _init, gated_rmsnorm, linear, linear_init, rmsnorm_init


def ssm_init(key, cfg, *, stacked: tuple = (), dtype=jnp.bfloat16):
    D, N = cfg.d_model, cfg.ssm_state
    DI = cfg.d_inner_p  # padded inner width (TP divisibility)
    H = cfg.ssm_heads_p
    ks = jax.random.split(key, 11)
    return {
        "wx": linear_init(ks[0], D, DI, dtype=dtype, stacked=stacked),
        "wz": linear_init(ks[1], D, DI, dtype=dtype, stacked=stacked),
        "wB": linear_init(ks[2], D, N, dtype=dtype, stacked=stacked),
        "wC": linear_init(ks[3], D, N, dtype=dtype, stacked=stacked),
        "wdt": linear_init(ks[4], D, H, dtype=dtype, stacked=stacked),
        "dt_bias": jnp.zeros((*stacked, H), jnp.float32),
        "A_log": _init(ks[5], (*stacked, H), 1.0, jnp.float32),
        "Dskip": jnp.ones((*stacked, H), jnp.float32),
        "conv_x": _init(ks[6], (*stacked, cfg.ssm_conv, DI), 1.0, dtype),
        "conv_B": _init(ks[7], (*stacked, cfg.ssm_conv, N), 1.0, dtype),
        "conv_C": _init(ks[8], (*stacked, cfg.ssm_conv, N), 1.0, dtype),
        "out_norm": rmsnorm_init(ks[9], DI, dtype, stacked),
        "wo": linear_init(ks[10], DI, D, dtype=dtype, stacked=stacked),
    }


def _causal_conv(x, w):
    """Depthwise causal conv along seq: x (B,S,C), w (K,C)."""
    K = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    out = jnp.zeros_like(x)
    for k in range(K):  # K is small (4); unrolled taps fuse into one kernel
        out = out + xp[:, k : k + x.shape[1], :] * w[k]
    return out


def _segsum(dA):
    """Cumulative within-chunk log-decay differences.
    dA: (..., Q) -> (..., Q, Q) lower-triangular sums dA[j+1..i]."""
    Q = dA.shape[-1]
    cs = jnp.cumsum(dA, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]  # sum over (j, i]
    mask = jnp.tril(jnp.ones((Q, Q), bool))
    return jnp.where(mask, diff, -jnp.inf)


def _chunk_decay(clog, rows: int):
    """Decay between chunks: clog (B,H,nc), each chunk's summed log-decay ->
    (B,H,rows,nc+1).  Entry [c, 0] takes the initial state, entry [c, z+1]
    the state chunk z leaves, to the start of chunk c: 0 for z >= c."""
    return jnp.exp(_segsum(jnp.pad(clog, ((0, 0), (0, 0), (1, 0)))))[:, :, :rows]


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def _carry_states(clog, states, rows: int):
    """The state entering each of ``rows`` chunks, from a zero initial state:
    out_c = sum_{z<c} exp(clog_{z+1..c-1}) states_z, one contraction over
    chunks (Listing 1 of arXiv:2405.21060).  states (B,nc,H,P,N) -> out
    (B,rows,H,P,N).  HIGHEST keeps the f32 of the recurrence: the TPU's
    default would round both operands to bfloat16."""
    return jnp.einsum("bhcz,bzhpn->bchpn", _chunk_decay(clog, rows)[..., 1:], states,
                      precision=lax.Precision.HIGHEST)


def _carry_states_fwd(clog, states, rows):
    out = _carry_states(clog, states, rows)
    return out, (clog, out)


def _carry_states_bwd(rows, res, g):
    # states_z enters the recurrence at the end of chunk z, so its gradient is
    # that of the state there, and clog_z scales the state entering chunk z:
    # d clog_z = exp(clog_z) <d states_z, out_z>.  That replaces the second
    # contraction over chunks autodiff would make for the decay matrix.
    clog, out = res
    nc = clog.shape[-1]
    g_states = jnp.einsum("bhcz,bchpn->bzhpn", _chunk_decay(clog, rows)[..., 1:], g,
                          precision=lax.Precision.HIGHEST)
    g_clog = jnp.exp(clog) * jnp.sum(g_states * out[:, :nc], axis=(3, 4)).transpose(0, 2, 1)
    return g_clog, g_states


_carry_states.defvjp(_carry_states_fwd, _carry_states_bwd)


def ssd_chunked(x, dt, A, Bm, Cm, chunk: int, init_state=None, return_state: bool = False,
                unroll: bool = False):
    """SSD scan.  x: (B,S,H,P), dt: (B,S,H) (post-softplus), A: (H,) negative,
    Bm/Cm: (B,S,N).  Returns y: (B,S,H,P) [, final_state (B,H,P,N)]."""
    Bsz, S, H, P = x.shape
    N = Bm.shape[-1]
    Q = min(chunk, S)
    nc = S // Q
    assert nc * Q == S, (S, Q)
    f32 = jnp.float32

    scope = jax.named_scope("ssd_kernel")
    scope.__enter__()
    xc = x.reshape(Bsz, nc, Q, H, P)
    dtc = dt.reshape(Bsz, nc, Q, H).astype(f32)
    Bc = Bm.reshape(Bsz, nc, Q, N)
    Cc = Cm.reshape(Bsz, nc, Q, N)
    dA = dtc * A  # (B,nc,Q,H) log-decay per step

    # within-chunk ("diagonal") term
    L = jnp.exp(_segsum(dA.transpose(0, 1, 3, 2)))  # (B,nc,H,Q,Q)
    scores = jnp.einsum("bcqn,bckn->bcqk", Cc.astype(f32), Bc.astype(f32))
    att = scores[:, :, None, :, :] * L  # (B,nc,H,Q,K); L zero above diagonal
    xdt = xc.astype(f32) * dtc[..., None]  # (B,nc,Q,H,P)
    y_diag = jnp.einsum("bchqk,bckhp->bcqhp", att, xdt)

    # per-chunk states
    cum = jnp.cumsum(dA, axis=2)  # (B,nc,Q,H)
    decay_end = jnp.exp(cum[:, :, -1:, :] - cum)  # (B,nc,Q,H)
    states = jnp.einsum("bckn,bckh,bckhp->bchpn", Bc.astype(f32), decay_end * dtc, xc.astype(f32))

    # inter-chunk recurrence S_c = exp(clog_c) S_{c-1} + states_c; prev
    # (B,nc,H,P,N) is the state entering each chunk
    if unroll:  # verification traces: one step per chunk, written out
        chunk_decay = jnp.exp(cum[:, :, -1, :])  # (B,nc,H)
        s0 = jnp.zeros((Bsz, H, P, N), f32) if init_state is None else init_state.astype(f32)

        def body(s_prev, inp):
            st, dec = inp  # (B,H,P,N), (B,H)
            s_new = s_prev * dec[:, :, None, None] + st
            return s_new, s_prev

        s_cur, prevs = s0, []
        for ci in range(nc):
            s_cur, pv = body(s_cur, (states[:, ci], chunk_decay[:, ci]))
            prevs.append(pv)
        sc, prev = s_cur, jnp.stack(prevs).transpose(1, 0, 2, 3, 4)
    else:  # closed form, no loop; row nc of ``out`` is the final state
        rows = nc + 1 if return_state else nc
        clog = cum[:, :, -1, :].transpose(0, 2, 1)  # (B,H,nc) log-decay of each chunk
        out = _carry_states(clog, states, rows)
        if init_state is not None:
            carry0 = _chunk_decay(clog, rows)[..., 0].transpose(0, 2, 1)  # (B,rows,H)
            out = out + carry0[..., None, None] * init_state.astype(f32)[:, None]
        prev, sc = out[:, :nc], (out[:, nc] if return_state else None)

    decay_start = jnp.exp(cum)  # (B,nc,Q,H)
    y_off = jnp.einsum("bcqn,bchpn,bcqh->bcqhp", Cc.astype(f32), prev, decay_start)

    y = (y_diag + y_off).reshape(Bsz, S, H, P)
    scope.__exit__(None, None, None)
    if return_state:
        return y, sc
    return y


@partial(jax.custom_vjp, nondiff_argnums=(5,))
def ssd_pallas(x, dt, A, Bm, Cm, chunk: int):
    """SSD scan through the Pallas kernel (Mosaic; TPU only).  The backward
    pass is the vjp of ``ssd_chunked``, recomputed from the saved inputs; it
    is loop-free, its inter-chunk recurrence one contraction over chunks."""
    # imported here: loading Pallas adds seconds to every cold verify
    from repro.kernels import ops as kops

    return kops.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk)


def _ssd_pallas_fwd(x, dt, A, Bm, Cm, chunk):
    return ssd_pallas(x, dt, A, Bm, Cm, chunk), (x, dt, A, Bm, Cm)


def _ssd_pallas_bwd(chunk, res, g):
    with jax.named_scope("ssd_bwd"):  # names the jnp backward's device ops
        _, vjp = jax.vjp(lambda *a: ssd_chunked(*a, chunk), *res)
        return vjp(g)


ssd_pallas.defvjp(_ssd_pallas_fwd, _ssd_pallas_bwd)


def ssm_fwd(cfg, ctx: ParallelCtx, p, x, *, impl: str = "reference",
            unroll: bool = False):
    """Full-sequence SSD block.  x: (B, S, D) replicated."""
    B, S, D = x.shape
    P = cfg.ssm_head_dim
    xproj = linear(p["wx"], x)  # (B,S,DI_loc) column-parallel over heads
    z = linear(p["wz"], x)
    Bm = linear(p["wB"], x)  # replicated (single SSD group)
    Cm = linear(p["wC"], x)
    dt_raw = linear(p["wdt"], x).astype(jnp.float32)  # (B,S,H_loc)... see below

    xproj = _causal_conv(xproj, p["conv_x"])
    Bm = _causal_conv(Bm, p["conv_B"])
    Cm = _causal_conv(Cm, p["conv_C"])
    xproj = jax.nn.silu(xproj)
    Bm = jax.nn.silu(Bm)
    Cm = jax.nn.silu(Cm)

    H_loc = xproj.shape[-1] // P
    # dt is head-wise; under TP wdt is column-sharded to the local heads
    dt = jax.nn.softplus(dt_raw + p["dt_bias"][..., :H_loc])
    A = -jnp.exp(p["A_log"][..., :H_loc].astype(jnp.float32))
    xh = xproj.reshape(B, S, H_loc, P)
    if impl == "pallas":
        y = ssd_pallas(xh, dt, A, Bm, Cm, cfg.ssm_chunk)
    else:
        y = ssd_chunked(xh, dt, A, Bm, Cm, cfg.ssm_chunk, unroll=unroll)
    y = y + (p["Dskip"][..., :H_loc])[..., None] * xh.astype(jnp.float32)
    y = y.reshape(B, S, H_loc * P).astype(x.dtype)
    y = gated_rmsnorm(p["out_norm"], y, z, cfg.norm_eps, group=cfg.ssm_head_dim)
    out = linear(p["wo"], y)  # row-parallel
    return ctx.sp_enter(out)


def ssm_init_cache(cfg, batch: int, tp_size: int = 1, dtype=jnp.bfloat16):
    P, N = cfg.ssm_head_dim, cfg.ssm_state
    H_loc = cfg.ssm_heads_p // tp_size
    DI_loc = H_loc * P
    K = cfg.ssm_conv
    return {
        "state": jnp.zeros((batch, H_loc, P, N), jnp.float32),
        "conv_x": jnp.zeros((batch, K - 1, DI_loc), dtype),
        "conv_B": jnp.zeros((batch, K - 1, N), dtype),
        "conv_C": jnp.zeros((batch, K - 1, N), dtype),
    }


def ssm_decode(cfg, ctx: ParallelCtx, p, x, cache):
    """Single-token SSD step: O(1) state update.  x: (B, 1, D)."""
    B = x.shape[0]
    P = cfg.ssm_head_dim
    xproj = linear(p["wx"], x)[:, 0]  # (B, DI_loc)
    z = linear(p["wz"], x)[:, 0]
    Bm = linear(p["wB"], x)[:, 0]
    Cm = linear(p["wC"], x)[:, 0]
    dt_raw = linear(p["wdt"], x)[:, 0].astype(jnp.float32)

    def conv_step(buf, new, w):
        # buf: (B, K-1, C) previous inputs; new: (B, C)
        full = jnp.concatenate([buf, new[:, None]], axis=1)  # (B,K,C)
        out = jnp.einsum("bkc,kc->bc", full, w)
        return out, full[:, 1:]

    cx, ncx = conv_step(cache["conv_x"], xproj, p["conv_x"])
    cB, ncB = conv_step(cache["conv_B"], Bm, p["conv_B"])
    cC, ncC = conv_step(cache["conv_C"], Cm, p["conv_C"])
    cx = jax.nn.silu(cx)
    cB = jax.nn.silu(cB).astype(jnp.float32)
    cC = jax.nn.silu(cC).astype(jnp.float32)

    H_loc = cx.shape[-1] // P
    dt = jax.nn.softplus(dt_raw + p["dt_bias"][..., :H_loc])  # (B,H)
    A = -jnp.exp(p["A_log"][..., :H_loc].astype(jnp.float32))
    xh = cx.reshape(B, H_loc, P).astype(jnp.float32)
    decay = jnp.exp(dt * A)  # (B,H)
    h = cache["state"] * decay[..., None, None] + jnp.einsum(
        "bh,bn,bhp->bhpn", dt, cB, xh
    )
    y = jnp.einsum("bn,bhpn->bhp", cC, h) + p["Dskip"][..., :H_loc, None] * xh
    y = y.reshape(B, 1, H_loc * P).astype(x.dtype)
    y = gated_rmsnorm(p["out_norm"], y, z[:, None], cfg.norm_eps, group=cfg.ssm_head_dim)
    out = linear(p["wo"], y)
    return ctx.sp_enter(out), {
        "state": h,
        "conv_x": ncx,
        "conv_B": ncB,
        "conv_C": ncC,
    }
