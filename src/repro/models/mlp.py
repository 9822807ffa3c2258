"""Feed-forward blocks: dense GLU/GELU MLPs and a dropless top-k MoE with
expert parallelism.

The execution MoE (``moe_fwd``) groups the routed (token, slot) pairs of the
experts a rank holds by expert, with static shapes and no sort (cumsum
ranks), runs them through grouped matrix products and combines the
per-token partial sum with one psum over the expert axis.  No token is
dropped.  ``moe_dense_fwd`` and ``moe_ep_fwd`` are the verifier's dense
formulations.  Padded experts (e.g. granite 40 -> 48) are masked out in the
router.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.parallel.ctx import ParallelCtx

from .modules import ACTS, linear, linear_init, _init


def mlp_init(key, cfg, d_ff: int, *, stacked: tuple = (), dtype=jnp.bfloat16):
    ks = jax.random.split(key, 3)
    if cfg.mlp_act in ("swiglu", "geglu"):
        return {
            "wg": linear_init(ks[0], cfg.d_model, d_ff, dtype=dtype, stacked=stacked),
            "wu": linear_init(ks[1], cfg.d_model, d_ff, dtype=dtype, stacked=stacked),
            "wo": linear_init(ks[2], d_ff, cfg.d_model, dtype=dtype, stacked=stacked),
        }
    return {
        "wi": linear_init(ks[0], cfg.d_model, d_ff, dtype=dtype, stacked=stacked),
        "wo": linear_init(ks[2], d_ff, cfg.d_model, dtype=dtype, stacked=stacked),
    }


def mlp_fwd(cfg, ctx: ParallelCtx, p, x):
    """Dense MLP: column-parallel in, row-parallel out, one psum."""
    if cfg.mlp_act == "swiglu":
        h = jax.nn.silu(linear(p["wg"], x)) * linear(p["wu"], x)
    elif cfg.mlp_act == "geglu":
        h = jax.nn.gelu(linear(p["wg"], x)) * linear(p["wu"], x)
    else:
        h = ACTS[cfg.mlp_act](linear(p["wi"], x))
    y = linear(p["wo"], h)
    return ctx.sp_enter(y)


# ---------------------------------------------------------------------------
# Mixture of Experts


def moe_init(key, cfg, *, stacked: tuple = (), dtype=jnp.bfloat16):
    E, D, F = cfg.experts, cfg.d_model, cfg.d_ff_expert
    ks = jax.random.split(key, 5)
    p = {
        "router": {"w": _init(ks[0], (*stacked, D, E), 1.0 / math.sqrt(D), jnp.float32)},
        "wg": _init(ks[1], (*stacked, E, D, F), 1.0 / math.sqrt(D), dtype),
        "wu": _init(ks[2], (*stacked, E, D, F), 1.0 / math.sqrt(D), dtype),
        "wo": _init(ks[3], (*stacked, E, F, D), 1.0 / math.sqrt(F), dtype),
    }
    if cfg.shared_expert_ff:
        p["shared"] = mlp_init(ks[4], cfg, cfg.shared_expert_ff, stacked=stacked, dtype=dtype)
    return p


# rows of a grouped-matmul tile on the TPU; the jnp path needs no tiles
GMM_TILE = 256


def _route(cfg, p, xf):
    """Softmax over the real experts, top-k, renormalised: (T, K) weights in
    float32 and expert ids.  Equals Granite's softmax over the top-k logits."""
    E = cfg.experts
    logits = (xf.astype(jnp.float32) @ p["router"]["w"]).astype(jnp.float32)
    if cfg.n_experts_padded and cfg.n_experts_padded > cfg.n_experts:
        pad_mask = jnp.arange(E) >= cfg.n_experts
        logits = jnp.where(pad_mask[None, :], -1e30, logits)
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, cfg.top_k)
    return w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9), idx


def _layout(idx, first, n_local: int, tm: int):
    """Where each routed (token, slot) pair lands in the grouped buffer of
    this rank's experts ``[first, first + n_local)``.

    Groups (local experts) start on ``tm``-row tile boundaries, are padded
    with zero rows to whole tiles and own at least one tile each; pairs
    routed elsewhere land nowhere.  Returns ``local`` (T, K), ``pos`` (T, K)
    (the row of each local pair; clipped for the others), ``row_pair``
    (M,) (the pair of each row; T*K for a padding row), ``tile_group``
    (M // tm,), ``n_tiles`` (1,) and the padded ``sizes`` (n_local,)."""
    T, K = idx.shape
    TK = T * K
    M = -(-TK // tm) * tm + n_local * tm  # every pair local, every group padded
    local = (idx >= first) & (idx < first + n_local)
    g = jnp.where(local, idx - first, n_local).reshape(TK)
    onehot = jax.nn.one_hot(g, n_local + 1, dtype=jnp.int32)
    rank = (jnp.cumsum(onehot, axis=0) * onehot).sum(-1) - 1  # within the group
    counts = onehot.sum(0)[:n_local]
    sizes = jnp.maximum(-(-counts // tm), 1) * tm
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    pos = jnp.where(g < n_local, starts[jnp.minimum(g, n_local - 1)] + rank, M)
    row_pair = jnp.full((M + 1,), TK, jnp.int32).at[pos].set(
        jnp.arange(TK, dtype=jnp.int32))[:M]
    tiles = jnp.arange(M // tm, dtype=jnp.int32)
    tile_group = jnp.minimum(jnp.searchsorted(ends // tm, tiles, side="right"),
                             n_local - 1).astype(jnp.int32)
    n_tiles = (ends[-1:] // tm).astype(jnp.int32)
    return local, jnp.minimum(pos, M - 1).reshape(T, K), row_pair, tile_group, n_tiles, sizes


@jax.custom_vjp
def _dispatch(x, row_tok, pos, local):
    """Rows of the grouped buffer: x[row_tok], a zero row where row_tok == T.
    Its vjp gathers each token's K rows back (no scatter)."""
    xe = jnp.concatenate([x, jnp.zeros((1, x.shape[1]), x.dtype)])
    return xe[row_tok]


def _dispatch_fwd(x, row_tok, pos, local):
    return _dispatch(x, row_tok, pos, local), (pos, local)


def _dispatch_bwd(res, g):
    pos, local = res
    dx = jnp.where(local[..., None], g[pos], 0).astype(jnp.float32).sum(1)
    return dx.astype(g.dtype), None, None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(out, cw, pos, local, row_tok, row_w):
    """y[t] = sum over local slots k of cw[t, k] * out[pos[t, k]] (float32
    accumulation); rows no local pair points at are never read.  Its vjp
    gathers too: d out[r] = row_w[r] * dy[row_tok[r]]."""
    rows = jnp.where(local[..., None], out[pos], 0).astype(jnp.float32)
    return jnp.einsum("tkd,tk->td", rows, cw).astype(out.dtype)


def _combine_fwd(out, cw, pos, local, row_tok, row_w):
    return _combine(out, cw, pos, local, row_tok, row_w), (out, cw, pos, local,
                                                         row_tok, row_w)


def _combine_bwd(res, dy):
    out, cw, pos, local, row_tok, row_w = res
    dye = jnp.concatenate([dy, jnp.zeros((1, dy.shape[1]), dy.dtype)]).astype(jnp.float32)
    d_out = (dye[row_tok] * row_w[:, None]).astype(out.dtype)
    rows = jnp.where(local[..., None], out[pos], 0).astype(jnp.float32)
    d_cw = jnp.einsum("tkd,td->tk", rows, dy.astype(jnp.float32))
    return d_out, d_cw, None, None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def moe_part(cfg, p, xf, first, n_local: int, *, impl: str = "reference"):
    """What the experts ``[first, first + n_local)`` give each token: xf
    (T, D) routed over all experts, the (token, slot) pairs routed to these
    experts grouped by expert (``_layout``), run through SwiGLU as grouped
    matrix products (the Pallas ``moe_gmm`` kernel under ``impl="pallas"``,
    ``jax.lax.ragged_dot`` otherwise), weighted and summed back per token.
    ``p``'s expert weights are these experts' (n_local, ...).  No pair is
    dropped: the buffer holds every pair even if all come to these experts."""
    T = xf.shape[0]
    K = cfg.top_k
    tm = GMM_TILE if impl == "pallas" else 1
    with jax.named_scope("moe_route"):
        w, idx = _route(cfg, p, xf)
        local, pos, row_pair, tile_group, n_tiles, sizes = _layout(idx, first, n_local, tm)
        cw = jnp.where(local, w, 0.0)
        row_tok = row_pair // K  # T for a padding row
        row_w = jnp.concatenate([cw.reshape(T * K), jnp.zeros((1,), cw.dtype)])[row_pair]
        rows = _dispatch(xf, row_tok, pos, local)

    with jax.named_scope("moe_experts"):
        if impl == "pallas":
            from repro.kernels import ops as kops

            mm = lambda a, wt: kops.moe_gmm(a, wt, tile_group, n_tiles, tm=tm)
        else:
            mm = lambda a, wt: jax.lax.ragged_dot(a, wt, sizes)
        h = jax.nn.silu(mm(rows, p["wg"])) * mm(rows, p["wu"])
        out = mm(h, p["wo"])

    with jax.named_scope("moe_combine"):
        return _combine(out, cw, pos, local, row_tok, row_w)


def moe_fwd(cfg, ctx: ParallelCtx, p, x, *, impl: str = "reference"):
    """Dropless top-k MoE.  x: (B, S, D), replicated across the expert axis.

    Each rank routes every token over all experts and computes the part
    its own experts give (``moe_part``); one psum over the expert axis adds
    the ranks' parts, and a shared expert is added once."""
    B, S, D = x.shape
    ep = ctx.ep_size if ctx.ep_axis else 1
    E_loc = cfg.experts // ep
    first = (jax.lax.axis_index(ctx.ep_axis) if ctx.ep_axis else 0) * E_loc
    y = moe_part(cfg, p, x.reshape(B * S, D), first, E_loc, impl=impl)
    if ctx.ep_axis:
        with jax.named_scope("moe_combine"):
            y = jax.lax.psum(y, ctx.ep_axis)
    y = y.reshape(B, S, D)
    if "shared" in p:
        # shared expert is column/row TP-sharded like a dense MLP: its output
        # is a partial sum and needs its own reduction (covered by
        # tests/test_granite_moe.py::test_expert_shares_sum_to_whole_layer)
        y = y + ctx.psum_tp(_shared_fwd(cfg, p["shared"], x))
    if ctx.sp and ctx.tp_axis:
        # under SP the caller expects a sequence-sharded activation; y is
        # replicated here so the local shard is just a slice
        chunk = S // ctx.tp_size
        r = jax.lax.axis_index(ctx.tp_axis)
        y = jax.lax.dynamic_slice_in_dim(y, r * chunk, chunk, axis=1)
    return y


def _dense_router_weights(cfg, p, xf):
    """Dense top-k routing mask (T, E) float32: softmax + top-k +
    renormalize, scattered back to a dense per-expert weight column."""
    T = xf.shape[0]
    E = cfg.experts
    K = cfg.top_k
    w, idx = _route(cfg, p, xf)
    dense_w = jnp.zeros((T, E), jnp.float32)
    tok = jnp.arange(T)[:, None].repeat(K, 1)
    return dense_w.at[tok.reshape(-1), idx.reshape(-1)].add(w.reshape(-1))


def moe_dense_fwd(cfg, ctx: ParallelCtx, p, x):
    """Dense-masked MoE formulation: every expert computes every token and a
    top-k weight mask combines them.  Numerically equals the dropless MoE;
    cost O(E/topk) higher — used for the *verification* graphs (static
    dataflow: all ops are einsums over the expert dim, TP shards the expert
    FFN width, one psum discharges).  The execution path is the dropless
    grouped MoE (moe_fwd)."""
    B, S, D = x.shape
    T = B * S
    xf = x.reshape(T, D)
    dense_w = _dense_router_weights(cfg, p, xf).astype(x.dtype)

    h = jax.nn.silu(jnp.einsum("td,edf->tef", xf, p["wg"])) * jnp.einsum(
        "td,edf->tef", xf, p["wu"])
    eout = jnp.einsum("tef,efd->ted", h, p["wo"])  # partial over sharded f
    y = jnp.einsum("ted,te->td", eout, dense_w)
    if ctx.tp_axis:
        y = jax.lax.psum(y, ctx.tp_axis)
    y = y.reshape(B, S, D)
    if "shared" in p:
        y = y + ctx.psum_tp(_shared_fwd(cfg, p["shared"], x))
    if ctx.sp and ctx.tp_axis:
        # under SP the caller expects a sequence-sharded activation; y is
        # replicated here so the local shard is just this rank's slice
        chunk = S // ctx.tp_size
        r = jax.lax.axis_index(ctx.tp_axis)
        y = jax.lax.dynamic_slice_in_dim(y, r * chunk, chunk, axis=1)
    return y


def moe_ep_fwd(cfg, ctx: ParallelCtx, p, x):
    """Expert-parallel dense-masked MoE (the EP *verification* formulation):
    each rank holds its expert slice of the stacked weights
    (``(E_loc, D, F)``, sharded over the expert dim), takes its slice of the
    dense routing mask by rank index, and accumulates the weighted local
    expert outputs as an **unrolled slice/add loop** discharged by one
    all_reduce over the expert axis — the paper's slice / loop_red_B /
    loop_red_D relation family (Fig. 8), now exercised by a whole-model
    scenario.  Numerically equals ``moe_dense_fwd``; with ``ctx.single()``
    (ep=1) the same code is the dense baseline whose add-chain over all E
    expert slices is exactly what ``loop_red_B`` matches."""
    B, S, D = x.shape
    T = B * S
    E = cfg.experts
    xf = x.reshape(T, D)
    dense_w = _dense_router_weights(cfg, p, xf).astype(x.dtype)

    ep = ctx.ep_size if ctx.ep_axis else 1
    E_loc = E // ep
    if ctx.ep_axis:
        first = jax.lax.axis_index(ctx.ep_axis) * E_loc
        dw = jax.lax.dynamic_slice_in_dim(dense_w, first, E_loc, axis=1)
    else:
        dw = dense_w  # (T, E) — the full dense mask

    # local expert compute: (T, E_loc, D); weights arrive expert-sharded
    h = jax.nn.silu(jnp.einsum("td,edf->tef", xf, p["wg"])) * jnp.einsum(
        "td,edf->tef", xf, p["wu"])
    eout = jnp.einsum("tef,efd->ted", h, p["wo"])
    weighted = eout * dw[:, :, None]  # (T, E_loc, D)

    # unrolled per-expert accumulation (slice -> add chain)
    acc = None
    for e in range(E_loc):
        chunk = jax.lax.slice_in_dim(weighted, e, e + 1, axis=1)  # (T, 1, D)
        acc = chunk if acc is None else acc + chunk
    if ctx.ep_axis:
        acc = jax.lax.psum(acc, ctx.ep_axis)
    y = acc.reshape(B, S, D)
    if "shared" in p:
        # EP scenarios keep non-expert params replicated: the shared expert
        # runs dense (psum_tp is the identity without a tp axis)
        y = y + ctx.psum_tp(_shared_fwd(cfg, p["shared"], x))
    return y


def _shared_fwd(cfg, p, x):
    if cfg.mlp_act == "geglu":
        h = jax.nn.gelu(linear(p["wg"], x)) * linear(p["wu"], x)
    elif "wg" in p:
        h = jax.nn.silu(linear(p["wg"], x)) * linear(p["wu"], x)
    else:
        h = ACTS[cfg.mlp_act](linear(p["wi"], x))
    return linear(p["wo"], h)
