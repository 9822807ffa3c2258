"""Pipeline scenario: each stage's TP parallelization verified in
isolation.  Stage boundaries are replicated hidden states — exactly what
``parallel/pipeline.py`` ships over its ppermute ring — so per-stage
equivalence composes to whole-pipeline equivalence."""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import AbstractMesh
from jax.sharding import PartitionSpec as P

from repro.core.spans import span
from repro.core.trace import trace_sharded
from repro.core.verifier import OutputSpec
from repro.models.model import _tree_index
from repro.models.modules import rmsnorm
from repro.parallel.ctx import ParallelCtx

from ..plan import TP_AXIS, PlanError
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


def stage_pair(arch: str, cfg, tp: int, stg: int, stages: int,
               batch: int, seq: int, ctx: BuildCtx = None) -> GraphPair:
    """Pipeline stage ``stg`` of ``stages``: the stage's layer slice (plus
    embedding frontend on stage 0 and final norm + head on the last stage)
    with TP sharding inside the stage."""
    ctx = ctx if ctx is not None else BuildCtx()
    if cfg.n_layers % stages:
        raise PlanError(
            f"{arch}: n_layers={cfg.n_layers} not divisible by "
            f"stages={stages} (pass layers=... to round)")
    per_stage = cfg.n_layers // stages
    lo, hi = stg * per_stage, (stg + 1) * per_stage
    first, last = stg == 0, stg == stages - 1

    with span("trace"):
        mesh = AbstractMesh((tp,), (TP_AXIS,))
        pctx = ParallelCtx(tp_axis=TP_AXIS, tp_size=tp, ep_axis=TP_AXIS,
                           ep_size=tp)
        model_s, model_d, param_shapes = model_pair(cfg, pctx)
        pspecs = verify_pspecs(param_shapes, cfg)
        b, seq = batch_avals(cfg, model_s, batch, seq)
        Pnum = cfg.block_period

        def stage_fn(model):
            def run(params, x_or_batch):
                if first:
                    x = model._inputs_to_hidden(params, x_or_batch)
                else:
                    x = x_or_batch
                positions = jnp.arange(seq)
                for li in range(lo, hi):
                    with jax.named_scope(f"layer{li}"):
                        lp = _tree_index(params["blocks"][li % Pnum], li // Pnum)
                        x = model._layer_fwd(lp, x, positions, li % Pnum,
                                             unroll=True)
                if last:
                    x = model.ctx.sp_exit(x)
                    x = rmsnorm(params["ln_f"], x, cfg.norm_eps)
                    return model._head(params, x)
                return x

            return run

        if first:
            x_aval = b
            xspec = jax.tree_util.tree_map(lambda _: P(), b)
        else:
            x_aval = jax.ShapeDtypeStruct((batch, seq, cfg.d_model),
                                          model_s.dtype)
            xspec = P()
        out_spec = P(None, None, TP_AXIS) if last else P()

        gb, b_in = ctx.trace_base(f"stage{stg}:{stages}", stage_fn(model_s),
                                  param_shapes, x_aval,
                                  name=f"{arch}-stage{stg}-base")
        gd, d_in, _ = trace_sharded(
            stage_fn(model_d), mesh, (pspecs, xspec), out_spec,
            param_shapes, x_aval, name=f"{arch}-stage{stg}-dist")
    return GraphPair(
        gb, gd, b_in, d_in,
        input_facts=spec_input_facts(flat_spec_leaves((pspecs, xspec)),
                                     axis=TP_AXIS),
        output_specs=[OutputSpec(kind="shard", dim=2) if last
                      else OutputSpec(kind="dup")],
        size=tp, axis=TP_AXIS, base_cached=ctx.base_cached)


@S.scenario("stage", TP_AXIS,
            doc="one pipeline stage in isolation (TP inside the stage)")
def stage(arch: str, cfg, plan, scen, ctx: BuildCtx) -> GraphPair:
    return stage_pair(arch, cfg, scen.size, scen.stage, plan.stages,
                      plan.scenario_batch(scen), plan.seq, ctx=ctx)
