"""Shared scenario-building plumbing: the trace/stamp/spec wiring every
scenario builder uses exactly once instead of hand-rolling it.

Moved here from ``repro.verify.pairs`` (now a deprecation shim): the
:class:`GraphPair` result type, the verification param-spec tables, shape
helpers, the stamping pipeline, and :class:`BuildCtx` — the handle the
:class:`~repro.verify.session.Session` threads through ``build_pair`` so
scenarios of one plan share the *base* (single-device) trace when their
program + avals coincide (cache keyed on ``(arch/cfg, program tag, aval
signature)``, not on the scenario name — ``Report.cache.base_trace_cached``
surfaces a hit).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.ir import Graph
from repro.core.spans import span
from repro.core.stamp import TRACE_PERIODS, stamp_graph
from repro.core.trace import LAYER_TAG_STRIDE, trace
from repro.models import Model
from repro.parallel.ctx import ParallelCtx
from repro.parallel.sharding import param_specs


@dataclass
class GraphPair:
    """A traced (baseline, distributed) pair plus its relation registration."""

    base: Graph
    dist: Graph
    base_inputs: list
    dist_inputs: list
    input_facts: list
    output_specs: list
    size: int
    axis: str
    stamped: bool = False
    base_cached: bool = False  # base trace served from the shared cache
    # every axis the dist program's mesh declares (empty = just ``axis``);
    # multi-axis scenarios set this so lint's ghost-axis check knows the
    # orthogonal axes are legitimate
    mesh_axes: tuple = ()


@dataclass
class BuildCtx:
    """Per-build context the Session hands to scenario builders.

    ``stamp`` toggles layer stamping; ``base_cache``/``base_key`` plug the
    session's shared base-trace store in (``None`` -> always trace)."""

    stamp: bool = True
    base_cache: Optional[dict] = None
    base_key: tuple = ()
    base_cached: bool = field(default=False, init=False)

    def trace_base(self, tag: str, fn, *avals, name: str = "base"):
        """Trace the baseline program, shared across scenarios: the cache is
        keyed on ``(base_key, tag, aval signature)`` so any two scenarios
        tracing the *same program over the same avals* reuse one trace."""
        return self._traced(tag, lambda: trace(fn, *avals, name=name), avals)

    def trace_base_sharded(self, tag: str, fn, mesh, in_specs, out_specs,
                           *avals, name: str = "dist"):
        """Sharded-trace variant of :meth:`trace_base` — the composite
        scenario's *baseline* is exactly tp-forward's distributed trace, so
        with matching shape knobs they share one.  ``tag`` must identify
        program + mesh + specs (the aval signature covers only shapes)."""
        from repro.core.trace import trace_sharded

        return self._traced(
            tag,
            lambda: trace_sharded(fn, mesh, in_specs, out_specs, *avals,
                                  name=name),
            avals)

    def _traced(self, tag: str, thunk, avals):
        if self.base_cache is None:
            g, in_ids, _ = thunk()
            return g, in_ids
        sig = (self.base_key, tag, _aval_sig(avals))
        hit = self.base_cache.get(sig)
        if hit is not None:
            self.base_cached = True
            return hit
        g, in_ids, _ = thunk()
        self.base_cache[sig] = (g, in_ids)
        return g, in_ids


def _aval_sig(avals) -> tuple:
    return tuple(
        (tuple(a.shape), str(a.dtype)) for a in jax.tree_util.tree_leaves(avals)
    )


# ------------------------------------------------------------- param specs
def verify_pspecs(param_shapes, cfg):
    """Param specs for the TP verification formulation: like execution
    specs, but MoE experts use FFN-width TP instead of expert parallelism."""
    specs = param_specs(param_shapes)

    def fix(path, spec, leaf):
        names = tuple(p.key if hasattr(p, "key") else str(p) for p in path)
        if len(names) >= 2 and names[-2] == "moe" and names[-1] in ("wg", "wu", "wo"):
            if names[-1] == "wo":
                return P(None, None, "model", None)  # (nb, E, F, D): shard F
            return P(None, None, None, "model")  # (nb, E, D, F): shard F
        return spec

    return jax.tree_util.tree_map_with_path(
        lambda pth, sp, lf: fix(pth, sp, lf), specs, param_shapes)


def ep_pspecs(param_shapes, cfg, axis: str):
    """Param specs for the EP verification formulation: MoE expert weights
    sharded over the *expert* dim (the execution sharding), everything else
    replicated — the scenario verifies the expert axis in isolation."""

    def fix(path, leaf):
        names = tuple(p.key if hasattr(p, "key") else str(p) for p in path)
        if len(names) >= 2 and names[-2] == "moe" and names[-1] in ("wg", "wu", "wo"):
            return P(None, axis, None, None)  # (nb, E, D|F, F|D): shard E
        return P(*([None] * len(leaf.shape)))

    return jax.tree_util.tree_map_with_path(fix, param_shapes)


# ------------------------------------------------------------ shape helpers
def round_layers(cfg, n_layers: Optional[int], stages: int = 1):
    """Round a layer-count override up to whole block periods (hybrids
    repeat every P layers) and, for pipeline plans, to equal stages."""
    if n_layers is None and stages <= 1:
        return cfg
    per = cfg.block_period
    n_layers = cfg.n_layers if n_layers is None else n_layers
    step = per * stages
    n_layers = max(step, (n_layers + step - 1) // step * step)
    return dataclasses.replace(cfg, n_layers=n_layers)


def batch_avals(cfg, model, batch: int, seq: int):
    """ShapeDtypeStruct batch inputs for a forward trace (modality-aware).
    Returns (b, seq) — vision frontends may grow seq."""
    b = {}
    if cfg.frontend == "vision_patches":
        seq = max(seq, cfg.frontend_len + 32)
        b["vision_embeds"] = jax.ShapeDtypeStruct(
            (batch, cfg.frontend_len, cfg.frontend_dim), model.dtype)
        b["tokens"] = jax.ShapeDtypeStruct((batch, seq - cfg.frontend_len), jnp.int32)
    elif cfg.frontend == "audio_frames":
        b["frames"] = jax.ShapeDtypeStruct((batch, seq, cfg.d_model), model.dtype)
    else:
        b["tokens"] = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    return b, seq


def model_pair(cfg, ctx: ParallelCtx, moe_impl: str = "dense"):
    """The (baseline, distributed) Model pair + shared param avals."""
    model_s = Model(cfg, ParallelCtx.single(), moe_impl=moe_impl)
    model_d = Model(cfg, ctx, moe_impl=moe_impl)
    param_shapes = jax.eval_shape(model_s.init, jax.random.PRNGKey(0))
    return model_s, model_d, param_shapes


# ----------------------------------------------------------------- stamping
def stamped_parts(cfg, pair_fn, periods_per_block: int):
    """Trace only TRACE_PERIODS block periods and stamp the rest, or None.

    ``periods_per_block``: layer tags per period region (block_period for
    forward traces whose periods span P layer scopes; 1 for decode traces
    whose period is one outer block scope).  The work is spanned ``trace``
    and ``stamp`` in the verdict open, if any (:mod:`repro.core.spans`)."""
    total = cfg.n_layers // cfg.block_period
    if total <= TRACE_PERIODS:
        return None
    cfg_t = dataclasses.replace(
        cfg, n_layers=TRACE_PERIODS * cfg.block_period)
    with span("trace"):
        gb, b_in, gd, d_in, flat_specs = pair_fn(cfg_t)
    with span("stamp"):
        stride = LAYER_TAG_STRIDE * periods_per_block
        sb = stamp_graph(gb, total, lambda t: t // stride)
        sd = None if sb is None else stamp_graph(gd, total,
                                                 lambda t: t // stride)
    if sd is None:
        return None
    return sb, b_in, sd, d_in, flat_specs


def stamped_or_full(cfg, pair_fn, periods_per_block: int, stamp: bool):
    """The standard stamped-with-fallback build: returns ``(parts,
    stamped)``, with the full trace of the fallback spanned ``trace``."""
    parts = stamped_parts(cfg, pair_fn, periods_per_block) if stamp else None
    if parts is not None:
        return parts, True
    with span("trace"):
        return pair_fn(cfg), False


def flat_spec_leaves(specs) -> list:
    return jax.tree_util.tree_leaves(specs, is_leaf=lambda x: isinstance(x, P))


__all__ = [
    "BuildCtx", "GraphPair", "batch_avals", "ep_pspecs",
    "flat_spec_leaves", "model_pair", "round_layers", "stamped_or_full",
    "stamped_parts", "verify_pspecs",
]
