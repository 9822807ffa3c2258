"""jit'd public wrappers for the Pallas kernels.

``interpret`` defaults to False: the kernels lower through Mosaic and run only
on a TPU.  Nothing here looks at the backend — on a CPU the default call
fails loudly instead of falling back to the Pallas interpreter; tests pass
``interpret=True`` explicitly to run the kernel bodies for validation.
The models reach these only through ``Model(..., impl="pallas")``; the
default ``impl="reference"`` runs the jnp implementations on every backend.
"""
from __future__ import annotations

from functools import partial

import jax

from . import flash_attention as _fa
from . import rmsnorm as _rn
from . import ssd_scan as _ssd


@partial(jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False):
    return _fa.flash_attention(q, k, v, causal=causal, block_q=block_q,
                               block_k=block_k, interpret=interpret)


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, A, Bm, Cm, *, chunk: int = 128, interpret: bool = False):
    return _ssd.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk, interpret=interpret)


@partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def rmsnorm(x, scale, *, eps: float = 1e-5, block_rows: int = 256,
            interpret: bool = False):
    return _rn.rmsnorm(x, scale, eps=eps, block_rows=block_rows,
                       interpret=interpret)
