"""Silent bugs planted in the distributed graph a verdict is asked about.

A plant names a kind, a layer of the plan and a draw; it rewrites one node
of that layer in a fresh copy of the graph and records where.  The kinds are
the two most common silent errors of a tensor-parallel program:

  drop_all_reduce  an all_reduce is bypassed: its partial sum flows on
  precision_drop   a matrix product runs in a lower type, then is cast back

Layers are counted in the order in which the graph's layer tags first
appear, so a plant's layer means the same in any graph of the same model.
A graph with no such layer, or whose layer holds no node of the kind, is
left as it is: the verdict is then asked about a plan that still has the
bug where the plant says, and a verifier that never looked at that layer
answers wrongly.
"""
from __future__ import annotations

from dataclasses import dataclass

LOWER = {"float32": "bfloat16", "bfloat16": "float16"}


@dataclass
class Plant:
    kind: str  # "drop_all_reduce" | "precision_drop"
    layer: int  # ordinal of the layer tag, in order of first appearance
    draw: int  # picks the node among the layer's candidates
    tag: object = None  # the planted node's layer tag, once planted

    def candidates(self, g) -> list:
        if self.kind == "drop_all_reduce":
            ok = lambda n: n.op == "all_reduce"
        elif self.kind == "precision_drop":
            ok = lambda n: n.op == "dot" and n.dtype in LOWER
        else:
            raise ValueError(f"unknown plant kind {self.kind!r}")
        tags = list(dict.fromkeys(n.layer for n in g if n.layer is not None))
        if self.layer >= len(tags):
            return []
        return [n.id for n in g if n.layer == tags[self.layer] and ok(n)]

    def __call__(self, g):
        """The mutated copy of graph ``g`` (``g`` itself when the layer has
        no candidate)."""
        from repro.core.ir import Graph

        cands = self.candidates(g)
        if not cands:
            return g
        target = cands[self.draw % len(cands)]
        ng, remap = Graph(g.name + "+plant"), {}
        for n in g:
            ins = [remap[i] for i in n.inputs]
            kw = dict(src=n.src, layer=n.layer, scope=n.scope)
            params = dict(n.params)
            if n.id == target and self.kind == "drop_all_reduce":
                remap[n.id] = ins[0]
                continue
            if n.id == target:
                low = ng.add(n.op, ins, n.shape, LOWER[n.dtype], params, **kw)
                remap[n.id] = ng.add("convert", [low], n.shape, n.dtype,
                                     {"new_dtype": n.dtype}, **kw)
                continue
            remap[n.id] = ng.add(n.op, ins, n.shape, n.dtype, params, **kw)
        ng.outputs = [remap[o] for o in g.outputs]
        self.tag = g[target].layer
        return ng
