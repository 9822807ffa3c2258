"""Composite scenario: ``tpdp-forward`` — the tp x dp 2D program verified
along the data axis against the 1D tensor-parallel per-device program.

Per-axis scenarios (tp-forward, dp-forward) each compare against the
single-device baseline and never check the *interaction* of the two axes.
The composite closes that gap with a chain argument:

    single-device  ==  TP per-device program      (tp-forward)
    TP per-device  ==  tp x dp per-device program (THIS scenario)

The 2D per-device program (weights sharded over "model", batch sharded over
"data") is verified with the TP program as its *baseline*: weight shards
are duplicates across data ranks, the batch input is data-sharded, and the
model-axis collectives appearing in BOTH graphs discharge through the
orthogonal-collective congruence rule (a collective over another mesh axis
applies the same deterministic function at every data rank, so it commutes
with stacking over the verified axis).  ``Plan(tp=T, dp=D,
composite=True)`` expands to [tp-forward, tpdp-forward].
"""
from __future__ import annotations

import time

import jax
from jax.sharding import AbstractMesh
from jax.sharding import PartitionSpec as P

from repro.core.trace import trace_sharded
from repro.core.verifier import OutputSpec
from repro.parallel.ctx import ParallelCtx

from ..plan import DP_AXIS, TP_AXIS, PlanError
from ..specs import spec_input_facts
from .harness import (
    BuildCtx,
    GraphPair,
    batch_avals,
    flat_spec_leaves,
    model_pair,
    verify_pspecs,
)
from .registry import DEFAULT_SCENARIOS as S


@S.scenario("tpdp-forward", DP_AXIS,
            doc="tp x dp composite forward: the 2D per-device program vs "
                "the 1D TP program (axis interaction)",
            requires="dense archs")
def tpdp_forward(arch: str, cfg, plan, scen, ctx: BuildCtx) -> GraphPair:
    dp, tp = scen.size, plan.tp
    batch = plan.scenario_batch(scen)
    if cfg.n_experts:
        raise PlanError(
            f"{arch}: dense-masked MoE gating scatters against local token "
            f"ids — composite plans for MoE archs are covered by numerical "
            f"tests")
    if batch % dp:
        raise PlanError(f"batch={batch} not divisible by dp={dp}")
    t0 = time.perf_counter()

    pctx = ParallelCtx(tp_axis=TP_AXIS, tp_size=tp, ep_axis=TP_AXIS, ep_size=tp)
    _, model_d, param_shapes = model_pair(cfg, pctx)  # baseline == TP program
    pspecs = verify_pspecs(param_shapes, cfg)
    b, seq = batch_avals(cfg, model_d, batch, plan.seq)

    fn = lambda p, bb: model_d.forward(p, bb, unroll=True)

    # baseline: the 1D TP per-device program over the full batch — the same
    # trace as tp-forward's distributed side, shared through the session's
    # base-trace cache when the shape knobs coincide (e.g. explicit batch=)
    mesh_tp = AbstractMesh((tp,), (TP_AXIS,))
    bspecs_tp = jax.tree_util.tree_map(lambda _: P(), b)
    gb, b_in = ctx.trace_base_sharded(
        f"fwd:dense:dist:tp{tp}",
        fn, mesh_tp, (pspecs, bspecs_tp), P(None, None, TP_AXIS),
        param_shapes, b, name=f"{arch}-tp-base")

    # distributed: the 2D (data, model) per-device program, batch sharded
    mesh_2d = AbstractMesh((dp, tp), (DP_AXIS, TP_AXIS))
    bspecs_2d = jax.tree_util.tree_map(lambda _: P(DP_AXIS), b)
    gd, d_in, _ = trace_sharded(
        fn, mesh_2d, (pspecs, bspecs_2d), P(DP_AXIS, None, TP_AXIS),
        param_shapes, b, name=f"{arch}-tpdp-dist")

    # relative to the data axis: per-shard weights are duplicates, the
    # batch input is sharded on dim 0 (model-axis sharding is invisible —
    # it is identical in both per-device programs)
    flat_specs = flat_spec_leaves((pspecs, bspecs_2d))
    return GraphPair(
        gb, gd, b_in, d_in,
        input_facts=spec_input_facts(flat_specs, axis=DP_AXIS),
        output_specs=[OutputSpec(kind="shard", dim=0)],
        size=dp, axis=DP_AXIS, mesh_axes=(DP_AXIS, TP_AXIS),
        trace_s=time.perf_counter() - t0, base_cached=ctx.base_cached)
