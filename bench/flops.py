"""Operations and bytes that a computation needs, from its shapes alone.

Only matrix products are counted as operations (two per multiply-add); the
element-wise work beside them is left out, so a roofline share read against
these counts is a lower bound of the true one, never above it.
"""
from __future__ import annotations


def matmul_flops(m: int, k: int, n: int) -> int:
    return 2 * m * k * n


def ssd_scan_cost(b: int, s: int, h: int, p: int, n: int, chunk: int,
                  x_bytes: int = 2, bc_bytes: int = 2, out_bytes: int = 4):
    """(flops, bytes) of one SSD scan call over x (b,s,h,p), dt (b,s,h)
    float32, B and C (b,s,n) shared by all heads (one group), y (b,s,h,p).

    Per chunk of q steps: C·Bᵀ once (it does not depend on the head), and per
    head the masked (q,q)·(q,p) product, the (q,n)·(n,p) read of the incoming
    state and the (n,q)·(q,p) state update.  Bytes: each input read once and
    the output written once."""
    q = min(chunk, s)
    c = s // q
    per_chunk = matmul_flops(q, n, q) + h * (
        matmul_flops(q, q, p) + matmul_flops(q, n, p) + matmul_flops(n, q, p))
    flops = b * c * per_chunk
    nbytes = (b * s * h * p * (x_bytes + out_bytes) + b * s * h * 4
              + 2 * b * s * n * bc_bytes + h * 4)
    return flops, nbytes


def ssd_flops_per_token(h: int, p: int, n: int, chunk: int) -> float:
    """Forward matrix-product operations of the SSD scan per token."""
    f, _ = ssd_scan_cost(1, chunk, h, p, n, chunk)
    return f / chunk


def roofline_s(flops: float, nbytes: float, peak_flops: float,
               peak_bytes_per_s: float):
    """The least time the chip could take, and which bound sets it."""
    tf, tb = flops / peak_flops, nbytes / peak_bytes_per_s
    return (tf, "compute") if tf >= tb else (tb, "memory")
