"""Pallas TPU kernel for the Mamba-2 SSD chunked scan.

Grid (B, H, n_chunks) with the chunk dim innermost: each (b, h) pair walks
its chunks sequentially, carrying the (N, P) SSM state in VMEM scratch —
the inter-chunk recurrence lives entirely in VMEM while the intra-chunk
work is three MXU matmuls (C·Bᵀ, (scores⊙L)·x, Bᵀ·x), exactly the structure
of Listing 1 in [arXiv:2405.21060] adapted to TPU tiling.

Layout: the wrapper moves heads ahead of the sequence so every block's last
two dims are (chunk, P), (chunk, N) or (1, chunk) — the TPU block rule needs
them to be (8, 128)-aligned or whole.  ``dt`` arrives as a lane-dense row per
head; its column form and the within-chunk cumulative decay are built with
masked reductions over a (Q, Q) iota (no in-kernel cumsum or transpose).
``A`` (one scalar per head) sits in SMEM.

Validated in interpret mode against the literal recurrence (ref.ssd_ref)
and the chunked jnp implementation in models/ssm.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, state_ref, *, q: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    f32 = jnp.float32
    x = x_ref[...].astype(f32)  # (Q, P)
    dt = dt_ref[...].astype(f32)  # (1, Q) row
    a = a_ref[pl.program_id(1)]  # scalar A_h (negative)
    bm = b_ref[...].astype(f32)  # (Q, N)
    cm = c_ref[...].astype(f32)  # (Q, N)

    row = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    tri = col <= row
    da = dt * a  # (1, Q) log-decay steps
    dt_col = jnp.sum(jnp.where(row == col, dt, 0.0), axis=1, keepdims=True)  # (Q, 1)
    cum_col = jnp.sum(jnp.where(tri, da, 0.0), axis=1, keepdims=True)  # (Q, 1)
    cum_row = jnp.sum(jnp.where(row <= col, dt_col * a, 0.0), axis=0,
                      keepdims=True)  # (1, Q)
    total = jnp.sum(da, axis=1, keepdims=True)  # (1, 1)

    # intra-chunk: L[i,j] = exp(cum_i - cum_j) for j <= i
    L = jnp.where(tri, jnp.exp(cum_col - cum_row), 0.0)
    scores = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=f32)  # (Q, Q)
    y_diag = jax.lax.dot_general(scores * L * dt, x, (((1,), (0,)), ((), ())),
                                 preferred_element_type=f32)  # (Q, P)

    # inter-chunk: contribution of the incoming state
    state = state_ref[...]  # (N, P)
    y_off = jnp.exp(cum_col) * jax.lax.dot_general(
        cm, state, (((1,), (0,)), ((), ())), preferred_element_type=f32)

    y_ref[...] = (y_diag + y_off).astype(y_ref.dtype)

    # state update: S' = S * exp(sum da) + Σ_k exp(cum_Q - cum_k) dt_k B_k x_k^T
    decay_end = jnp.exp(total - cum_col) * dt_col  # (Q, 1)
    state_ref[...] = state * jnp.exp(total) + jax.lax.dot_general(
        bm * decay_end, x, (((0,), (0,)), ((), ())),
        preferred_element_type=f32)  # (N, P)


def ssd_scan(x, dt, A, Bm, Cm, *, chunk: int = 128, interpret: bool = False):
    """x: (B,S,H,P); dt: (B,S,H); A: (H,); Bm/Cm: (B,S,N) -> y (B,S,H,P) f32."""
    Bsz, S, H, P = x.shape
    N = Bm.shape[-1]
    q = min(chunk, S)
    n_c = S // q
    assert n_c * q == S, (S, q)

    xt = x.transpose(0, 2, 1, 3)  # (B, H, S, P)
    dtt = dt.astype(jnp.float32).transpose(0, 2, 1)[:, :, None, :]  # (B, H, 1, S)
    kernel = functools.partial(_ssd_kernel, q=q)
    y = pl.pallas_call(
        kernel,
        grid=(Bsz, H, n_c),
        in_specs=[
            pl.BlockSpec((None, None, q, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((None, None, 1, q), lambda b, h, c: (b, h, 0, c)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((None, q, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((None, q, N), lambda b, h, c: (b, c, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, q, P), lambda b, h, c: (b, h, c, 0)),
        out_shape=jax.ShapeDtypeStruct((Bsz, H, S, P), jnp.float32),
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        interpret=interpret,
    )(xt, dtt, A.astype(jnp.float32), Bm, Cm)
    return y.transpose(0, 2, 1, 3)
