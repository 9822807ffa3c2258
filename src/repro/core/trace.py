"""jaxpr -> TensorIR extraction (the paper's "IR graph generation" stage).

The paper instruments PyTorch-XLA/NeuronX to dump IR graphs with source-level
debug metadata.  In JAX all of that is native: ``jax.make_jaxpr`` gives the IR,
``eqn.source_info.traceback`` gives file:line, and ``name_stack`` gives the
``jax.named_scope`` path we use for layer tagging and vendor-kernel-granularity
meta rules.

``trace`` inlines ``pjit``/``remat``/``custom_*`` calls and — crucially —
``shard_map``: the inner jaxpr of a shard-mapped function is the **per-device
program with explicit collectives** (psum/all_gather/...), which is exactly
the "distributed graph" Scalify verifies.
"""
from __future__ import annotations

import hashlib
import re
from typing import Any, Callable, Optional, Sequence

import jax
import numpy as np

from .ir import Graph
from .spans import span

# jaxpr primitive -> IR op (1:1 renames; anything absent falls through opaque)
_PRIM_MAP = {
    "dot_general": "dot",
    "convert_element_type": "convert",
    "broadcast_in_dim": "broadcast",
    "concatenate": "concat",
    "select_n": "select",
    "psum": "all_reduce",
    "pmax": "all_reduce",
    "pmin": "all_reduce",
    "reduce_precision": "convert",
    "stop_gradient": "copy",
    "copy": "copy",
    "add_any": "add",  # autodiff cotangent accumulation == add
    "squeeze": "reshape",
    "expand_dims": "reshape",
    "log_softmax": "log_softmax",
    "exp2": "exp2",
}
_REDUCE_PRIMS = {
    "reduce_sum": "reduce_sum",
    "reduce_max": "reduce_max",
    "reduce_min": "reduce_min",
    "reduce_prod": "reduce_prod",
    "reduce_and": "reduce_and",
    "reduce_or": "reduce_or",
    "argmax": "argmax",
    "argmin": "argmin",
}
_INLINE_CALL_PRIMS = {
    "pjit",
    "jit",
    "closed_call",
    "core_call",
    "custom_jvp_call",
    "custom_vjp_call",
    "custom_vjp_call_jaxpr",
    "remat",
    "checkpoint",
    "remat2",
    "custom_lin",
}

_PSUM_OPS = {"psum": "add", "pmax": "max", "pmin": "min"}


def _src_of(eqn) -> str:
    try:
        tb = eqn.source_info.traceback
        if tb is None:
            return ""
        for fr in tb.frames:
            f = fr.file_name
            if "site-packages" in f or "/jax/" in f or f.startswith("<"):
                continue
            return f"{f.rsplit('/', 1)[-1]}:{fr.line_num}"
        return ""
    except Exception:
        return ""


def _scope_of(eqn) -> str:
    try:
        return str(eqn.source_info.name_stack)
    except Exception:
        return ""


_LAYER_RE = re.compile(r"(?:^|/)layer[_]?(\d+)")
_SUB_RE = re.compile(r"(?:^|/)sub(\d+)")

# tag stride between consecutive ``layer<i>`` scopes: room for per-layer
# sub-scopes (decode blocks) without colliding with the next layer's tag
LAYER_TAG_STRIDE = 4096


def default_layer_tag(scope: str) -> Optional[int]:
    m = _LAYER_RE.search(scope)
    if m is None:
        return None
    tag = int(m.group(1)) * LAYER_TAG_STRIDE
    ms = _SUB_RE.search(scope)
    if ms is not None:  # block-level scope with per-layer sub-scopes (decode)
        tag += int(ms.group(1)) + 1
    return tag


def _const_hash(val) -> str:
    arr = np.asarray(val)
    return hashlib.sha1(
        arr.tobytes() + str(arr.shape).encode() + str(arr.dtype).encode()
    ).hexdigest()[:16]


def _collective_params(prim: str, params: dict) -> dict:
    out: dict[str, Any] = {}
    axes = params.get("axes") or params.get("axis_name")
    if isinstance(axes, str):
        axes = (axes,)
    out["axes"] = tuple(axes) if axes else ()
    groups = params.get("axis_index_groups")
    out["groups"] = "full" if groups is None else tuple(map(tuple, groups))
    if prim in _PSUM_OPS:
        out["reduce_op"] = _PSUM_OPS[prim]
    if prim == "all_gather":
        out["all_gather_dimension"] = params.get("all_gather_dimension", 0)
        out["tiled"] = params.get("tiled", False)
    if prim == "reduce_scatter":
        out["scatter_dimension"] = params.get("scatter_dimension", 0)
        out["tiled"] = params.get("tiled", False)
        out["reduce_op"] = "add"
    if prim == "all_to_all":
        out["split_axis"] = params.get("split_axis")
        out["concat_axis"] = params.get("concat_axis")
        out["tiled"] = params.get("tiled", False)
    if prim == "ppermute":
        out["perm"] = tuple(map(tuple, params.get("perm", ())))
    if prim == "axis_index":
        out["axes"] = (params.get("axis_name"),)
    return out


class Tracer:
    def __init__(self, layer_tag_fn: Callable[[str], Optional[int]] = default_layer_tag,
                 scan_inline: bool = False):
        self.g = Graph()
        self.layer_tag_fn = layer_tag_fn
        # outer (global-shape) input id -> per-shard input id (shard_map inline)
        self.sharded_input_remap: dict[int, int] = {}
        # scan_inline: trace scan bodies once, tagging nodes with the product
        # of enclosing trip counts ("mult") — used for exact collective/FLOP
        # accounting in the roofline analysis.
        self.scan_inline = scan_inline
        self._mult = 1
        # node id -> concrete value for int/bool scalar consts, so scalar
        # index arithmetic folds at trace time (see _try_fold)
        self._scalar_val: dict[int, Any] = {}
        # hash-consed const nodes: unrolled layers re-create identical
        # literals/closure consts per layer; dedup keeps the graph small and
        # makes repeated layers reference period-invariant leaves (required
        # by layer stamping; sound because the e-graph already merges
        # equal-payload consts into one e-class)
        self._const_cache: dict[tuple, int] = {}

    def _add_const(self, shape, dtype, value_hash: Optional[str], val=None) -> int:
        key = (value_hash, tuple(shape), str(dtype))
        if value_hash is not None:
            hit = self._const_cache.get(key)
            if hit is not None:
                return hit
        cparams: dict[str, Any] = {"value_hash": value_hash}
        if val is not None and not np.any(np.asarray(val)):
            # all-zero payload: rules about additive identities (scatter-add
            # gradient accumulation, zero-padding of partial sums) key on this
            cparams["zero"] = True
        if val is not None:
            arr = np.asarray(val)
            if arr.shape == () and arr.dtype.kind in "ib":
                # scalar int/bool payload carried on the node: rank-indexed
                # slicing rules (sliceops.rank_dynamic_slice) match the chunk
                # constant in ``axis_index * chunk`` start computations
                cparams["value"] = int(arr)
        nid = self.g.add("const", (), shape, dtype, cparams)
        if val is not None:
            self._record_scalar(nid, val)
        if value_hash is not None:
            self._const_cache[key] = nid
        return nid

    def _record_scalar(self, nid: int, val) -> int:
        arr = np.asarray(val)
        if arr.shape == () and arr.dtype.kind in "ib":
            self._scalar_val[nid] = arr
        return nid

    # Scalar integer constant folding: index-clamp chains (dynamic_update_
    # slice lowers start clamping to select/lt/add against the *dim size*)
    # otherwise differ structurally between baseline and per-device graphs
    # (global vs local dim) even though both evaluate to the same constant —
    # folding canonicalizes both sides so congruence matching relates them.
    _FOLD_PRIMS = {
        "add": lambda a, b: a + b,
        "sub": lambda a, b: a - b,
        "mul": lambda a, b: a * b,
        "max": np.maximum,
        "min": np.minimum,
        "neg": np.negative,
        "rem": np.fmod,  # lax.rem is C-style truncated (sign of dividend)
        "lt": np.less,
        "le": np.less_equal,
        "gt": np.greater,
        "ge": np.greater_equal,
        "eq": np.equal,
        "ne": np.not_equal,
        "clamp": lambda lo, x, hi: np.clip(x, lo, hi),
        "select_n": lambda which, *cases: cases[int(which)],
        "convert_element_type": lambda x: x,
    }

    def _try_fold(self, prim: str, eqn, in_ids: list[int]) -> Optional[int]:
        fn = self._FOLD_PRIMS.get(prim)
        if fn is None or len(eqn.outvars) != 1:
            return None
        aval = eqn.outvars[0].aval
        if tuple(aval.shape) != () or np.dtype(aval.dtype).kind not in "ib":
            return None
        if any(i not in self._scalar_val for i in in_ids):
            return None
        val = np.asarray(fn(*[self._scalar_val[i] for i in in_ids]))
        val = val.astype(np.dtype(aval.dtype))
        return self._add_const((), str(aval.dtype), _const_hash(val), val)

    def _emit_eqn(self, eqn, in_ids: list[int]) -> list[int]:
        prim = eqn.primitive.name
        src, scope = _src_of(eqn), _scope_of(eqn)
        layer = self.layer_tag_fn(scope)
        outs = []

        def add(op: str, params: Optional[dict] = None, which_out: int = 0) -> int:
            ov = eqn.outvars[which_out]
            params = dict(params or {})
            if self._mult != 1:
                params["mult"] = self._mult
            return self.g.add(
                op,
                in_ids,
                tuple(ov.aval.shape),
                str(ov.aval.dtype),
                params,
                src=src,
                layer=layer,
                scope=scope,
            )

        params = dict(eqn.params)
        if prim in ("reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
                    "reduce_and", "reduce_or", "argmax", "argmin"):
            outs.append(add(_REDUCE_PRIMS[prim], {"axes": tuple(params.get("axes", ()))}))
        elif prim == "dot_general":
            dn = params["dimension_numbers"]
            dn = tuple(tuple(tuple(x) for x in side) for side in dn)
            outs.append(add("dot", {"dimension_numbers": dn}))
        elif prim == "convert_element_type" or prim == "reduce_precision":
            outs.append(add("convert", {"new_dtype": str(eqn.outvars[0].aval.dtype)}))
        elif prim == "broadcast_in_dim":
            outs.append(
                add(
                    "broadcast",
                    {
                        "shape": tuple(params["shape"]),
                        "broadcast_dimensions": tuple(params["broadcast_dimensions"]),
                    },
                )
            )
        elif prim == "reshape" or prim == "squeeze" or prim == "expand_dims":
            outs.append(add("reshape", {"new_sizes": tuple(eqn.outvars[0].aval.shape)}))
        elif prim == "transpose":
            outs.append(add("transpose", {"permutation": tuple(params["permutation"])}))
        elif prim == "slice":
            outs.append(
                add(
                    "slice",
                    {
                        "start_indices": tuple(params["start_indices"]),
                        "limit_indices": tuple(params["limit_indices"]),
                        "strides": tuple(params["strides"]) if params.get("strides") else None,
                    },
                )
            )
        elif prim == "split":
            # split (e.g. the transpose of concatenate) == one slice per piece
            axis, off = params["axis"], 0
            shape = eqn.invars[0].aval.shape
            for i, size in enumerate(params["sizes"]):
                start = [0] * len(shape)
                limit = list(shape)
                start[axis], limit[axis] = off, off + size
                off += size
                outs.append(add("slice", {"start_indices": tuple(start),
                                          "limit_indices": tuple(limit),
                                          "strides": None}, which_out=i))
        elif prim == "concatenate":
            outs.append(add("concat", {"dimension": params["dimension"]}))
        elif prim in ("psum", "pmax", "pmin", "all_gather", "reduce_scatter",
                      "all_to_all", "ppermute", "axis_index"):
            op = {
                "psum": "all_reduce", "pmax": "all_reduce", "pmin": "all_reduce",
                "all_gather": "all_gather", "reduce_scatter": "reduce_scatter",
                "all_to_all": "all_to_all", "ppermute": "ppermute",
                "axis_index": "axis_index",
            }[prim]
            cparams = _collective_params(prim, params)
            for i, _ in enumerate(eqn.outvars):
                outs.append(add(op, cparams, which_out=i))
        elif prim == "iota":
            outs.append(add("iota", {"dimension": params.get("dimension", 0),
                                     "shape": tuple(eqn.outvars[0].aval.shape)}))
        elif prim in ("dynamic_slice", "dynamic_update_slice", "gather", "scatter",
                      "scatter-add", "scatter_add", "pad", "rev", "sort", "top_k",
                      "cumsum", "cumlogsumexp", "cummax", "select_n"):
            name = {"select_n": "select", "scatter-add": "scatter_add"}.get(prim, prim)
            keep = {
                k: v
                for k, v in params.items()
                if isinstance(v, (int, float, bool, str, tuple, list))
            }
            if prim == "gather" or prim.startswith("scatter"):
                dn = params.get("dimension_numbers")
                keep["dimension_numbers"] = str(dn)
                keep["slice_sizes"] = tuple(params.get("slice_sizes", ()) or ())
            for i, _ in enumerate(eqn.outvars):
                outs.append(add(name, keep, which_out=i))
        else:
            ew = _PRIM_MAP.get(prim, prim)
            keep = {
                k: v
                for k, v in params.items()
                if isinstance(v, (int, float, bool, str)) and k not in ("sharding",)
            }
            for i, _ in enumerate(eqn.outvars):
                outs.append(add(ew, keep, which_out=i))
        return outs

    def trace_jaxpr(self, jaxpr, consts: Sequence[Any], in_ids: list[int], env=None) -> list[int]:
        env: dict[Any, int] = dict(env or {})

        def read(var) -> int:
            if hasattr(var, "val"):  # Literal
                return self._add_const(
                    tuple(np.shape(var.val)),
                    str(np.asarray(var.val).dtype),
                    _const_hash(var.val),
                    var.val,
                )
            return env[var]

        for cv, cval in zip(jaxpr.constvars, consts):
            aval = cv.aval
            env[cv] = self._add_const(
                tuple(aval.shape),
                str(aval.dtype),
                _const_hash(cval) if cval is not None else None,
                cval,
            )
        for iv, nid in zip(jaxpr.invars, in_ids):
            env[iv] = nid

        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            ins = [read(v) for v in eqn.invars]
            if prim in _INLINE_CALL_PRIMS:
                closed = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
                inner = closed.jaxpr if hasattr(closed, "jaxpr") else closed
                iconsts = closed.consts if hasattr(closed, "consts") else []
                if prim in ("custom_jvp_call", "custom_vjp_call"):
                    ins = ins[: len(inner.invars)]
                out_ids = self.trace_jaxpr(inner, iconsts, ins)
                for ov, oid in zip(eqn.outvars, out_ids):
                    env[ov] = oid
                continue
            if prim == "shard_map":
                inner = eqn.params["jaxpr"]
                # shard_map body sees *per-shard* shapes; re-issue any outer
                # input/const operand whose shape changes as a fresh leaf node
                # with the per-shard aval (the verification registers facts
                # against these per-shard leaves).
                inner_ins = []
                for outer_id, iv in zip(ins, inner.invars):
                    node = self.g[outer_id]
                    ishape = tuple(iv.aval.shape)
                    if node.op in ("input", "param", "const") and node.shape != ishape:
                        nid = self.g.add(
                            node.op,
                            (),
                            ishape,
                            str(iv.aval.dtype),
                            dict(node.params),
                            src=node.src,
                            layer=node.layer,
                            scope=node.scope,
                        )
                        self.sharded_input_remap[outer_id] = nid
                        inner_ins.append(nid)
                    else:
                        inner_ins.append(outer_id)
                out_ids = self.trace_jaxpr(inner, getattr(inner, "consts", []) or [], inner_ins)
                for ov, oid in zip(eqn.outvars, out_ids):
                    env[ov] = oid
                continue
            if prim == "scan":
                closed = eqn.params["jaxpr"]
                length = eqn.params.get("length") or 1
                if self.scan_inline:
                    # trace the body ONCE with mult multiplied by trip count;
                    # body invars: [consts..., carry..., xs-slices...] — feed
                    # carry/const operands, synthesize leaves for xs slices.
                    inner = closed.jaxpr if hasattr(closed, "jaxpr") else closed
                    iconsts = closed.consts if hasattr(closed, "consts") else []
                    n_consts = eqn.params.get("num_consts", 0)
                    n_carry = eqn.params.get("num_carry", 0)
                    body_ins = list(ins[: n_consts + n_carry])
                    for iv in inner.invars[n_consts + n_carry:]:
                        body_ins.append(
                            self.g.add("input", (), tuple(iv.aval.shape),
                                       str(iv.aval.dtype), {"scan_slice": True})
                        )
                    self._mult *= length
                    out_ids = self.trace_jaxpr(inner, iconsts, body_ins)
                    self._mult //= length
                    # outvars: [carry..., stacked ys...]; map both to body outs
                    for i, ov in enumerate(eqn.outvars):
                        env[ov] = out_ids[i] if i < len(out_ids) else out_ids[-1]
                    continue
                # opaque scan: one node with body fingerprint (full-model
                # verification unrolls layers in Python instead; see models)
                body_repr = str(closed.jaxpr if hasattr(closed, "jaxpr") else closed)
                h = hashlib.sha1(body_repr.encode()).hexdigest()[:16]
                src, scope = _src_of(eqn), _scope_of(eqn)
                for i, ov in enumerate(eqn.outvars):
                    env[ov] = self.g.add(
                        "scan",
                        ins,
                        tuple(ov.aval.shape),
                        str(ov.aval.dtype),
                        {"body_hash": h, "length": length, "out": i},
                        src=src,
                        scope=scope,
                    )
                continue
            folded = self._try_fold(prim, eqn, ins)
            if folded is not None:
                env[eqn.outvars[0]] = folded
                continue
            out_ids = self._emit_eqn(eqn, ins)
            for ov, oid in zip(eqn.outvars, out_ids):
                env[ov] = oid
        return [read(v) for v in jaxpr.outvars]


def trace(
    fn: Callable,
    *avals,
    param_tree: Any = None,
    layer_tag_fn: Callable[[str], Optional[int]] = default_layer_tag,
    name: str = "graph",
    scan_inline: bool = False,
) -> tuple[Graph, list[int], list[int]]:
    """Trace ``fn(*avals)`` to a TensorIR Graph.

    Returns ``(graph, input_node_ids, output_node_ids)`` where input ids are
    in flattened-argument order (register sharding facts against these).

    ``scan_inline=True`` traces scan bodies once with a ``mult`` param equal
    to the product of enclosing trip counts — for FLOP/collective accounting
    only (stacked-output shapes are not reconstructed), not for verification.
    """
    with span("trace.jaxpr"):
        closed = jax.make_jaxpr(fn)(*avals)
    t = Tracer(layer_tag_fn, scan_inline=scan_inline)
    t.g.name = name
    flat_avals = jax.tree_util.tree_leaves(avals)
    in_ids = [
        t.g.add("input", (), tuple(a.shape), str(a.dtype), {"arg": i})
        for i, a in enumerate(flat_avals)
    ]
    with span("trace.ir"):
        out_ids = t.trace_jaxpr(closed.jaxpr, closed.consts, in_ids)
    t.g.mark_output(*out_ids)
    # outer global-shape leaf -> per-shard re-issued leaf (layer stamping
    # grows the dead outer leaves alongside their per-shard aliases)
    t.g.input_alias = dict(t.sharded_input_remap)
    in_ids = [t.sharded_input_remap.get(i, i) for i in in_ids]
    return t.g, in_ids, out_ids


def trace_sharded(
    fn: Callable,
    mesh,
    in_specs,
    out_specs,
    *avals,
    layer_tag_fn: Callable[[str], Optional[int]] = default_layer_tag,
    name: str = "dist",
    check_vma: bool = False,
) -> tuple[Graph, list[int], list[int]]:
    """Trace the **per-device** program of ``shard_map(fn)`` (collectives
    explicit).  ``avals`` are *global* shapes; input nodes carry per-shard
    shapes as seen by the device program."""
    from jax import shard_map

    sm = shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                   check_vma=check_vma)
    return trace(sm, *avals, layer_tag_fn=layer_tag_fn, name=name)
