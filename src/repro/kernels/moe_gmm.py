"""Pallas TPU grouped matrix products over the experts a rank holds.

The rows of ``lhs`` are laid out in row tiles of ``tm``, each tile owned by one
group (expert): group ``g`` starts on a tile boundary, its rows are padded with
zero rows to a whole number of tiles, and every group owns at least one tile.
``tile_group[i]`` names the group of tile ``i``; only the first ``n_tiles``
tiles hold rows, so the grid visits those alone (a grid size read at run time,
from the routing).

  gmm   out[tile i] = lhs[tile i] @ rhs[tile_group[i]]       (m, n)
  tgmm  out[g]      = sum over tiles i of g: lhs[i]^T @ rhs[i]  (groups, k, n)

``gmm`` with ``transpose_rhs`` multiplies by ``rhs[g]^T``: the gradient of
``lhs``.  ``tgmm`` is the gradient of ``rhs``.  Rows of tiles at or past
``n_tiles`` are left unwritten by ``gmm``: the caller never reads them.  Each
call multiplies a whole row tile by a whole (k, n) weight block, so k and n
stay the expert's widths (Granite: 1536 and 512), and the weight block is
fetched once per group because the tiles of a group are consecutive.

On CPU this runs with ``interpret=True`` and is checked against
``jax.lax.ragged_dot``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gmm_kernel(group_ref, n_ref, lhs_ref, rhs_ref, out_ref, *, transpose_rhs: bool):
    del group_ref, n_ref
    dims = (((1,), (1,)), ((), ())) if transpose_rhs else (((1,), (0,)), ((), ()))
    out_ref[...] = jax.lax.dot_general(
        lhs_ref[...], rhs_ref[0], dims, preferred_element_type=jnp.float32
    ).astype(out_ref.dtype)


def gmm(lhs, rhs, tile_group, n_tiles, *, tm: int, transpose_rhs: bool = False,
        interpret: bool = False):
    """lhs (m, k), rhs (groups, k, n) [(groups, n, k) with ``transpose_rhs``],
    tile_group (m // tm,) int32, n_tiles (1,) int32 -> (m, n) in lhs's type."""
    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    rblock = (1, n, k) if transpose_rhs else (1, k, n)
    kernel = lambda *refs: _gmm_kernel(*refs, transpose_rhs=transpose_rhs)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_tiles[0],),
            in_specs=[
                pl.BlockSpec((tm, k), lambda i, grp, nt: (i, 0)),
                pl.BlockSpec(rblock, lambda i, grp, nt: (grp[i], 0, 0)),
            ],
            out_specs=pl.BlockSpec((tm, n), lambda i, grp, nt: (i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="moe_gmm",
    )(tile_group, n_tiles, lhs, rhs)


def _tgmm_kernel(group_ref, n_ref, lhs_ref, rhs_ref, out_ref, acc_ref):
    i = pl.program_id(0)
    last = n_ref[0] - 1
    g = group_ref[i]
    starts = (i == 0) | (group_ref[jnp.maximum(i - 1, 0)] != g)
    ends = (i == last) | (group_ref[jnp.minimum(i + 1, last)] != g)

    @pl.when(starts)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # (tm, k)^T @ (tm, n): transpose the tile in float32, as Mosaic lays it out
    lhs_t = lhs_ref[...].astype(jnp.float32).T.astype(lhs_ref.dtype)
    acc_ref[...] += jnp.dot(lhs_t, rhs_ref[...], preferred_element_type=jnp.float32)

    @pl.when(ends)
    def _store():
        out_ref[0] = acc_ref[...].astype(out_ref.dtype)


def tgmm(lhs, rhs, tile_group, n_tiles, *, groups: int, tm: int,
         interpret: bool = False):
    """lhs (m, k), rhs (m, n) -> (groups, k, n) in lhs's type: per group, the
    product of its rows of lhs, transposed, with its rows of rhs.  Every group
    owns at least one tile, so every output block is written."""
    m, k = lhs.shape
    n = rhs.shape[1]
    return pl.pallas_call(
        _tgmm_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_tiles[0],),
            in_specs=[
                pl.BlockSpec((tm, k), lambda i, grp, nt: (i, 0)),
                pl.BlockSpec((tm, n), lambda i, grp, nt: (i, 0)),
            ],
            out_specs=pl.BlockSpec((1, k, n), lambda i, grp, nt: (grp[i], 0, 0)),
            scratch_shapes=[pltpu.VMEM((k, n), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((groups, k, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="moe_tgmm",
    )(tile_group, n_tiles, lhs, rhs)
