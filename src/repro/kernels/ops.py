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
from . import moe_gmm as _gmm
from . import rmsnorm as _rn
from . import ssd_scan as _ssd


@partial(jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret",
                                   "scale"))
def flash_attention(q, k, v, *, causal: bool = True, block_q: int | None = None,
                    block_k: int | None = None, interpret: bool = False,
                    scale: float | None = None):
    return _fa.flash_attention(q, k, v, causal=causal, block_q=block_q,
                               block_k=block_k, interpret=interpret, scale=scale)


def moe_gmm(lhs, rhs, tile_group, n_tiles, *, tm: int, interpret: bool = False):
    """Grouped matmul over tile-aligned groups (``moe_gmm.gmm``), with its vjp:
    the lhs gradient is ``gmm`` against the transposed weights, the weight
    gradient ``tgmm``."""
    return _moe_gmm(lhs, rhs, tile_group, n_tiles, tm, interpret)


@partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _moe_gmm(lhs, rhs, tile_group, n_tiles, tm, interpret):
    return _gmm.gmm(lhs, rhs, tile_group, n_tiles, tm=tm, interpret=interpret)


def _moe_gmm_fwd(lhs, rhs, tile_group, n_tiles, tm, interpret):
    out = _gmm.gmm(lhs, rhs, tile_group, n_tiles, tm=tm, interpret=interpret)
    return out, (lhs, rhs, tile_group, n_tiles)


def _moe_gmm_bwd(tm, interpret, res, g):
    lhs, rhs, tile_group, n_tiles = res
    d_lhs = _gmm.gmm(g, rhs, tile_group, n_tiles, tm=tm, transpose_rhs=True,
                     interpret=interpret)
    d_rhs = _gmm.tgmm(lhs, g, tile_group, n_tiles, groups=rhs.shape[0], tm=tm,
                      interpret=interpret)
    return d_lhs, d_rhs, None, None


_moe_gmm.defvjp(_moe_gmm_fwd, _moe_gmm_bwd)


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, A, Bm, Cm, *, chunk: int = 128, interpret: bool = False):
    return _ssd.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk, interpret=interpret)


@partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def rmsnorm(x, scale, *, eps: float = 1e-5, block_rows: int = 256,
            interpret: bool = False):
    return _rn.rmsnorm(x, scale, eps=eps, block_rows=block_rows,
                       interpret=interpret)
