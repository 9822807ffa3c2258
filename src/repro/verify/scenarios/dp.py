"""Data-parallel scenarios: ``dp-forward`` (batch-sharded forward — catches
improper cross-batch interaction) and ``dp-grad`` (the DP gradient-sync
contract: per-device sum-loss gradients + psum == full-batch gradients).

DP scenarios skip MoE archs: the dense-masked gating scatters against
*local* token ids (data-dependent indexing outside the relational
language); those paths are covered by numerical equivalence tests.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import AbstractMesh
from jax.sharding import PartitionSpec as P

from repro.core.spans import span
from repro.core.trace import trace_sharded
from repro.core.verifier import OutputSpec
from repro.parallel.ctx import ParallelCtx

from ..plan import DP_AXIS, PlanError
from ..specs import spec_input_facts
from .harness import (
    BuildCtx,
    GraphPair,
    batch_avals,
    flat_spec_leaves,
    model_pair,
)
from .registry import DEFAULT_SCENARIOS as S


def _dp_setup(arch: str, cfg, dp: int, batch: int, seq: int):
    if cfg.n_experts:
        raise PlanError(
            f"{arch}: dense-masked MoE gating scatters against local token "
            f"ids — DP plans for MoE archs are covered by numerical tests")
    if batch % dp:
        raise PlanError(f"batch={batch} not divisible by dp={dp}")
    mesh = AbstractMesh((dp,), (DP_AXIS,))
    pctx = ParallelCtx(dp_axis=(DP_AXIS,), dp_size=dp)
    model_s, model_d, param_shapes = model_pair(cfg, pctx)
    pspecs = jax.tree_util.tree_map(lambda _: P(), param_shapes)
    b, seq = batch_avals(cfg, model_s, batch, seq)
    bspecs = jax.tree_util.tree_map(lambda _: P(DP_AXIS), b)
    return mesh, model_s, model_d, param_shapes, pspecs, b, bspecs


def dp_forward_pair(arch: str, cfg, dp: int, batch: int, seq: int,
                    ctx: BuildCtx = None) -> GraphPair:
    """Batch-sharded forward equivalence over the data axis: params
    replicated, inputs sharded on dim 0, logits sharded on dim 0 — proves
    the model has no improper cross-batch interaction under DP."""
    ctx = ctx if ctx is not None else BuildCtx()
    with span("trace"):
        mesh, model_s, model_d, param_shapes, pspecs, b, bspecs = _dp_setup(
            arch, cfg, dp, batch, seq)

        base_fn = lambda p, bb: model_s.forward(p, bb, unroll=True)
        dist_fn = lambda p, bb: model_d.forward(p, bb, unroll=True)
        gb, b_in = ctx.trace_base("fwd:dense", base_fn, param_shapes, b,
                                  name=f"{arch}-dp-base")
        gd, d_in, _ = trace_sharded(
            dist_fn, mesh, (pspecs, bspecs), P(DP_AXIS),
            param_shapes, b, name=f"{arch}-dp-dist")
    return GraphPair(
        gb, gd, b_in, d_in,
        input_facts=spec_input_facts(flat_spec_leaves((pspecs, bspecs)),
                                     axis=DP_AXIS),
        output_specs=[OutputSpec(kind="shard", dim=0)],
        size=dp, axis=DP_AXIS, base_cached=ctx.base_cached)


@S.scenario("dp-forward", DP_AXIS,
            doc="batch-sharded forward (catches cross-batch interaction)",
            requires="dense archs")
def dp_forward(arch: str, cfg, plan, scen, ctx: BuildCtx) -> GraphPair:
    return dp_forward_pair(arch, cfg, scen.size, plan.scenario_batch(scen),
                           plan.seq, ctx=ctx)


def dp_grad_pair(arch: str, cfg, dp: int, batch: int, seq: int,
                 ctx: BuildCtx = None) -> GraphPair:
    """The DP gradient-sync contract: per-device gradients of the local
    sum-loss, all-reduced over the data axis, must equal the full-batch
    gradients.  Sum-loss (not mean) keeps both sides free of batch-size
    constants — the mean/`1/dp` rescaling is pure scalar algebra applied
    identically by the trainer on both sides."""
    ctx = ctx if ctx is not None else BuildCtx()
    with span("trace"):
        mesh, model_s, model_d, param_shapes, pspecs, b, bspecs = _dp_setup(
            arch, cfg, dp, batch, seq)

        def base_fn(p, bb):
            return jax.grad(
                lambda q: model_s.forward(q, bb, unroll=True)
                .astype(jnp.float32).sum())(p)

        def dist_fn(p, bb):
            g = jax.grad(
                lambda q: model_d.forward(q, bb, unroll=True)
                .astype(jnp.float32).sum())(p)
            return jax.tree_util.tree_map(lambda a: jax.lax.psum(a, DP_AXIS),
                                          g)

        gb, b_in = ctx.trace_base("grad", base_fn, param_shapes, b,
                                  name=f"{arch}-grad-base")
        gd, d_in, _ = trace_sharded(
            dist_fn, mesh, (pspecs, bspecs),
            jax.tree_util.tree_map(lambda _: P(), param_shapes),
            param_shapes, b, name=f"{arch}-grad-dist")
    n_out = len(jax.tree_util.tree_leaves(param_shapes))
    return GraphPair(
        gb, gd, b_in, d_in,
        input_facts=spec_input_facts(flat_spec_leaves((pspecs, bspecs)),
                                     axis=DP_AXIS),
        output_specs=[OutputSpec(kind="dup")] * n_out,
        size=dp, axis=DP_AXIS, base_cached=ctx.base_cached)


@S.scenario("dp-grad", DP_AXIS,
            doc="per-device sum-loss gradients + psum vs full-batch grads",
            requires="dense archs")
def dp_grad(arch: str, cfg, plan, scen, ctx: BuildCtx) -> GraphPair:
    return dp_grad_pair(arch, cfg, scen.size, plan.scenario_batch(scen),
                        plan.seq, ctx=ctx)
