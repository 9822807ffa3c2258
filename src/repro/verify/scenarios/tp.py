"""Tensor-parallel scenarios: ``tp-forward`` (Megatron column/row TP,
vocab-parallel embedding/head) and ``tp-decode`` (one serving step against
head-sharded KV/SSM caches — the paper's own inference-graph setting).

Layers are unrolled under named scopes (per-layer memoization) and deep
models are layer-stamped; MoE layers use the dense-masked formulation with
expert-FFN TP (the capacity-dispatch execution path is data-dependent
scatter/gather and is covered by numerical equivalence tests — see
DESIGN.md §Arch-applicability).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import AbstractMesh
from jax.sharding import PartitionSpec as P

from repro.core.trace import trace_sharded
from repro.core.verifier import OutputSpec
from repro.parallel.ctx import ParallelCtx

from ..plan import TP_AXIS, PlanError
from ..specs import spec_input_facts, spec_output_specs
from .harness import (
    BuildCtx,
    GraphPair,
    batch_avals,
    flat_spec_leaves,
    model_pair,
    stamped_or_full,
    verify_pspecs,
)
from .registry import DEFAULT_SCENARIOS as S


def _tp_forward_parts(arch: str, cfg, tp: int, batch: int, seq: int,
                      ctx: BuildCtx, sp: bool = False):
    """Trace the (baseline, per-device) TP forward pair for ``cfg``."""
    mesh = AbstractMesh((tp,), (TP_AXIS,))
    pctx = ParallelCtx(tp_axis=TP_AXIS, tp_size=tp, ep_axis=TP_AXIS,
                       ep_size=tp, sp=sp)
    model_s, model_d, param_shapes = model_pair(cfg, pctx)
    pspecs = verify_pspecs(param_shapes, cfg)
    b, seq = batch_avals(cfg, model_s, batch, seq)
    bspecs = jax.tree_util.tree_map(lambda _: P(), b)

    base_fn = lambda p, bb: model_s.forward(p, bb, unroll=True)
    dist_fn = lambda p, bb: model_d.forward(p, bb, unroll=True)

    gb, b_in = ctx.trace_base("fwd:dense", base_fn, param_shapes, b,
                              name=f"{arch}-base")
    gd, d_in = ctx.trace_base_sharded(
        f"fwd:dense:dist:tp{tp}{':sp' if sp else ''}",
        dist_fn, mesh, (pspecs, bspecs), P(None, None, TP_AXIS),
        param_shapes, b, name=f"{arch}-dist")
    return gb, b_in, gd, d_in, flat_spec_leaves((pspecs, bspecs))


def tp_forward_pair(arch: str, cfg, tp: int, batch: int, seq: int,
                    stamp: bool = True, ctx: BuildCtx = None) -> GraphPair:
    ctx = ctx if ctx is not None else BuildCtx(stamp=stamp)
    pair_fn = lambda c: _tp_forward_parts(arch, c, tp, batch, seq, ctx)
    parts, stamped = stamped_or_full(
        cfg, pair_fn, cfg.block_period, ctx.stamp)
    gb, b_in, gd, d_in, flat_specs = parts
    return GraphPair(
        gb, gd, b_in, d_in,
        input_facts=spec_input_facts(flat_specs, axis=TP_AXIS),
        output_specs=[OutputSpec(kind="shard", dim=2)],
        size=tp, axis=TP_AXIS,
        stamped=stamped, base_cached=ctx.base_cached)


@S.scenario("tp-forward", TP_AXIS,
            doc="baseline forward vs TP/EP-sharded per-device forward")
def tp_forward(arch: str, cfg, plan, scen, ctx: BuildCtx) -> GraphPair:
    return tp_forward_pair(arch, cfg, scen.size, plan.scenario_batch(scen),
                           plan.seq, ctx=ctx)


def _tp_decode_parts(arch: str, cfg, tp: int, batch: int, max_len: int,
                     ctx: BuildCtx):
    """Trace the (baseline, per-device) decode-step pair for ``cfg``."""
    from repro.parallel.sharding import cache_specs as _cache_specs

    mesh = AbstractMesh((tp,), (TP_AXIS,))
    pctx = ParallelCtx(tp_axis=TP_AXIS, tp_size=tp, ep_axis=TP_AXIS, ep_size=tp)
    model_s, model_d, param_shapes = model_pair(cfg, pctx)
    pspecs = verify_pspecs(param_shapes, cfg)
    cache_shapes = jax.eval_shape(lambda: model_s.init_cache(batch, max_len))
    cspecs = _cache_specs(cache_shapes, None)
    tok = jax.ShapeDtypeStruct((batch,), jnp.int32)
    pos = jax.ShapeDtypeStruct((), jnp.int32)

    base_fn = lambda p, t, c, q: model_s.decode_step(p, t, c, q, unroll=True)
    dist_fn = lambda p, t, c, q: model_d.decode_step(p, t, c, q, unroll=True)
    gb, b_in = ctx.trace_base("decode", base_fn, param_shapes, tok,
                              cache_shapes, pos, name=f"{arch}-decode-base")
    gd, d_in, _ = trace_sharded(
        dist_fn, mesh, (pspecs, P(), cspecs, P()),
        (P(None, TP_AXIS), jax.tree_util.tree_map(lambda s: s, cspecs)),
        param_shapes, tok, cache_shapes, pos, name=f"{arch}-decode-dist")
    flat_specs = flat_spec_leaves((pspecs, P(), cspecs, P()))
    return gb, b_in, gd, d_in, (flat_specs, cspecs)


def tp_decode_pair(arch: str, cfg, tp: int, batch: int, max_len: int,
                   stamp: bool = True, ctx: BuildCtx = None) -> GraphPair:
    """The paper's own setting (inference graphs): one token against KV/SSM
    caches sharded over heads, vocab-parallel head output."""
    if cfg.encoder_only:
        raise PlanError(f"{arch} is encoder-only: no decode step")
    ctx = ctx if ctx is not None else BuildCtx(stamp=stamp)
    # one decode period = one outer block scope (P sub-layers)
    pair_fn = lambda c: _tp_decode_parts(arch, c, tp, batch, max_len, ctx)
    parts, stamped = stamped_or_full(
        cfg, pair_fn, 1, ctx.stamp)
    gb, b_in, gd, d_in, (flat_specs, cspecs) = parts

    # outputs: logits sharded over vocab (dim 1) + every cache leaf sharded
    # on its head dim (matching the input cache specs)
    cache_leaves = flat_spec_leaves(cspecs)
    out_specs = ([OutputSpec(kind="shard", dim=1)]
                 + spec_output_specs(cache_leaves, axis=TP_AXIS))
    return GraphPair(
        gb, gd, b_in, d_in,
        input_facts=spec_input_facts(flat_specs, axis=TP_AXIS),
        output_specs=out_specs,
        size=tp, axis=TP_AXIS,
        stamped=stamped, base_cached=ctx.base_cached)


@S.scenario("tp-decode", TP_AXIS,
            doc="one serving step against head-sharded KV/SSM caches")
def tp_decode(arch: str, cfg, plan, scen, ctx: BuildCtx) -> GraphPair:
    return tp_decode_pair(arch, cfg, scen.size, plan.scenario_batch(scen),
                          plan.max_len, ctx=ctx)
