"""Scope meta rules (vendor-kernel granularity, paper §5.1): match named-
scope regions against trusted templates.  The template is the *same
function* the framework uses to generate the region
(parallel/collectives.py); structural identity is checked by fingerprint,
so any mutation of the region stays unverified.

Meta rules scan the whole graph (regions straddle partition stages); the
group scan is cached on the Propagator — the graph is static.  Both engines
re-apply them after each pass / worklist drain until they fire nothing new.
"""
from __future__ import annotations

from ..bijection import Layout
from ..relations import DUP, PARTIAL, SHARD, Fact

# template fingerprints are pure functions of (variant, shapes, dtype, size):
# cache process-wide, like the old Propagator class attribute did
_vp_embed_templates: dict = {}


def apply_meta_rules(prop) -> None:
    if not hasattr(prop, "_meta_groups"):
        groups: dict[str, list[int]] = {}
        for n in prop.dist:
            parts = n.scope.split("/")
            if "vp_embed" in parts or "vp_embed_sp" in parts:
                groups.setdefault(n.scope, []).append(n.id)
        prop._meta_groups = []
        for scope, nids in groups.items():
            # scope tags are lost inside library internals (jnp.take's
            # custom_jvp); the region is the contiguous trace span
            lo, hi = min(nids), max(nids)
            span = [
                i for i in range(lo, hi + 1)
                if prop.dist[i].op not in ("input", "param")
            ]
            prop._meta_groups.append((span, scope))
    for span, scope in prop._meta_groups:
        _meta_vp_embed(prop, span, scope)


def _meta_vp_embed(prop, nids: list[int], scope: str = "vp_embed") -> None:
    g = prop.dist
    inside = set(nids)
    # "vp_embed_sp": the sequence-parallel variant — the region is the
    # *partial* (masked local lookup, no reduction); the escaping node is
    # the mask product and it earns a partial(add) fact the downstream
    # reduce_scatter discharges through the ordinary collective rule.
    partial = "vp_embed_sp" in scope.split("/")
    if partial:
        outs = [nid for nid in nids
                if g[nid].op == "mul"
                and (any(c not in inside for c in g.consumers(nid))
                     or nid in g.outputs)]
    else:
        # region output: the all_reduce whose consumers escape the region
        outs = [nid for nid in nids
                if g[nid].op == "all_reduce"
                and (any(c not in inside for c in g.consumers(nid))
                     or nid in g.outputs)]
    if len(outs) != 1 or prop.store.verified(outs[0]):
        return
    out = outs[0]
    # external inputs: the sharded table + the replicated ids
    ext = []
    for nid in nids:
        for i in g[nid].inputs:
            if i not in inside and i not in ext:
                ext.append(i)
    table = ids = None
    tfact = ifact = None
    for e in ext:
        for f in prop.store.facts(e):
            if f.kind == SHARD and prop._shard_src_dim(f) == 0 and len(g[e].shape) == 2:
                table, tfact = e, f
            elif f.kind == DUP and f.layout.is_identity and "int" in g[e].dtype:
                ids, ifact = e, f
    if table is None or ids is None:
        return
    # template fingerprint: trace the trusted generator with these shapes
    if not _vp_embed_template_ok(prop, nids, g[table].shape, g[ids].shape,
                                 g[table].dtype, partial=partial):
        prop.store.diag(
            out, "layout_mismatch",
            "vp_embed region deviates from the trusted template")
        return
    # baseline counterpart: gather(full_table, idx) with idx derived from
    # ids through layout-only ops (jnp.take inserts a broadcast)
    def derives_from(nid: int, target: int, depth: int = 8) -> bool:
        if prop.base_eg.same(nid, target):
            return True
        if depth == 0:
            return False
        n = prop.base[nid]
        # jnp.take inserts clip (max/min against consts) + broadcast; all
        # value-preserving for in-range token ids on the trusted baseline
        if n.op in ("broadcast", "reshape", "transpose", "convert", "max",
                    "min", "clamp", "select", "add", "lt", "ge"):
            return any(derives_from(i, target, depth - 1) for i in n.inputs)
        return False

    for zid in prop.base.consumers(tfact.base):
        z = prop.base[zid]
        if z.op == "gather" and len(z.inputs) == 2 and derives_from(
                z.inputs[1], ifact.base) and z.dtype == g[out].dtype:
            if partial:
                prop.emit(Fact(PARTIAL, zid, out, prop.size,
                               Layout.identity(z.shape), reduce_op="add"))
            else:
                prop.emit(Fact(DUP, zid, out, prop.size,
                               Layout.identity(z.shape)))
            prop.store.covered_scopes.add(scope)
            prop.store.covered_nodes.update(nids)
            return


def _vp_embed_template_ok(prop, nids, table_shape, ids_shape, dtype,
                          partial: bool = False) -> bool:
    key = (partial, tuple(table_shape), tuple(ids_shape), dtype, prop.size)
    if key not in _vp_embed_templates:
        import jax
        import jax.numpy as jnp
        from jax.sharding import AbstractMesh
        from jax.sharding import PartitionSpec as P

        from repro.parallel.collectives import vp_embed, vp_embed_partial

        from ..trace import trace_sharded

        mesh = AbstractMesh((prop.size,), (prop.axis,))
        tbl = jax.ShapeDtypeStruct((table_shape[0] * prop.size, table_shape[1]),
                                   dtype)
        idv = jax.ShapeDtypeStruct(tuple(ids_shape), jnp.int32)
        gen = vp_embed_partial if partial else vp_embed
        gt, t_in, _ = trace_sharded(
            lambda t, i: gen(t, i, prop.axis), mesh,
            (P(prop.axis, None), P()), P(), tbl, idv)
        body = [n.id for n in gt if n.op not in ("input", "param", "const")]
        _vp_embed_templates[key] = gt.fingerprint(sorted(body),
                                                  normalize_slices=True)
    region_fp = prop.dist.fingerprint(
        sorted(n for n in nids if prop.dist[n].op not in ("const",)),
        normalize_slices=True)
    # consts participate as ext leaves in both fingerprints via inputs
    return region_fp == _vp_embed_templates[key]
