"""Layer stamping (repro.core.stamp): the stamped graph must be node-by-node
identical to a full trace, verdicts/facts must match with stamping (and
worklist sharding) on vs off, and the memo fast path must actually serve
stamped layers from the template cache (MemoStats counters)."""
import dataclasses

import pytest

from repro.configs import get_config
from repro.core.ir import Graph
from repro.core.modelverify import verify_model_tp
from repro.verify.pairs import (
    _stamped_parts,
    _tp_decode_parts as _decode_pair,
    _tp_forward_parts as _forward_pair,
    round_layers as _round_layers,
)
from repro.verify.specs import spec_input_facts as _spec_input_facts


def _stamped_pair(cfg, pair_fn, periods_per_block):
    return _stamped_parts(cfg, pair_fn, periods_per_block)
from repro.core.partition import partition_layers
from repro.core.rules import Propagator, WorklistEngine
from repro.core.stamp import TRACE_PERIODS, stamp_graph
from repro.core.trace import LAYER_TAG_STRIDE
from repro.core.verifier import VerifyOptions

TP = 2


def _smoke_cfg(arch: str, n_layers: int):
    return dataclasses.replace(get_config(arch, smoke=True), n_layers=n_layers)


def _assert_graphs_equal(stamped: Graph, full: Graph) -> None:
    assert len(stamped.nodes) == len(full.nodes)
    for a, b in zip(stamped.nodes, full.nodes):
        assert a == b, f"node {a.id}:\n  stamped: {a}\n  full:    {b}"
    assert stamped.outputs == full.outputs


@pytest.mark.parametrize("arch", ["llama3_8b", "jamba_1_5_large"])
def test_stamped_forward_equals_full_trace(arch):
    cfg = get_config(arch, smoke=True)
    per = cfg.block_period
    total = 6 if per > 1 else 8
    pair_fn = lambda c: _forward_pair(arch, c, TP, 1, 16)
    stamped = _stamped_pair(_smoke_cfg(arch, total * per), pair_fn, per)
    assert stamped is not None, "periodic trace must stamp, not fall back"
    sb, b_in, sd, d_in, _ = stamped
    assert sb.stamp is not None and sd.stamp is not None
    fb, fb_in, fd, fd_in, _ = pair_fn(_smoke_cfg(arch, total * per))
    _assert_graphs_equal(sb, fb)
    _assert_graphs_equal(sd, fd)
    assert b_in == fb_in and d_in == fd_in


def test_stamped_decode_equals_full_trace():
    arch, total = "llama3_8b", 8
    pair_fn = lambda c: _decode_pair(arch, c, TP, 2, 64)
    stamped = _stamped_pair(_smoke_cfg(arch, total), pair_fn, 1)
    assert stamped is not None
    sb, _, sd, _, _ = stamped
    fb, _, fd, _, _ = pair_fn(_smoke_cfg(arch, total))
    _assert_graphs_equal(sb, fb)
    _assert_graphs_equal(sd, fd)


def test_stamp_verdict_parity():
    reports = {
        stamp: verify_model_tp("llama3_8b", tp=TP, smoke=True, n_layers=8,
                               seq=16, options=VerifyOptions(stamp=stamp))
        for stamp in (False, True)
    }
    on, off = reports[True], reports[False]
    assert on.verified and off.verified
    assert on.outputs_ok == off.outputs_ok
    assert on.num_facts == off.num_facts
    assert on.unverified_count == off.unverified_count


def test_memo_fast_path_stats():
    rep = verify_model_tp("llama3_8b", tp=TP, smoke=True, n_layers=8, seq=16)
    m = rep.memo
    assert rep.verified
    # layers 1..7 are structural clones of layer 0's steady state
    assert m.memo_hits >= 6, m
    # every stamped period (beyond the 3 traced) serves its fingerprint and
    # ext-input lists from the template cache
    assert m.fp_cached >= 8 - TRACE_PERIODS - 1, m
    # memo hits settle their nodes: no cleanup re-dispatch
    assert m.settled_nodes > 0, m


def _fact_keys(gb, b_in, gd, d_in, flat_specs, workers: int):
    """Drive per-layer worklist rewriting (as PartitionedVerifier does,
    without memoization) and return the derived fact-key set."""
    prop = Propagator(gb, gd, TP)
    eng = WorklistEngine(prop, workers=workers)
    for f in _spec_input_facts(flat_specs):
        b, d = b_in[f.base_index], d_in[f.dist_index]
        if f.kind == "dup":
            prop.register_dup(b, d)
        else:
            prop.register_shard(b, d, f.dim)
    try:
        for plan in partition_layers(gb, gd):
            if plan.dist_nodes:
                eng.run(plan.dist_nodes)
        eng.run()
    finally:
        eng.close()
    return {f.key() for facts in prop.store.by_dist.values() for f in facts}


def test_fact_set_parity_stamp_and_shard():
    """Identical fact sets with stamping on vs off and with the sharded
    parallel sweep on vs off (the acceptance property of this pipeline)."""
    arch, total = "llama3_8b", 6
    pair_fn = lambda c: _forward_pair(arch, c, TP, 1, 16)
    stamped = _stamped_pair(_smoke_cfg(arch, total), pair_fn, 1)
    assert stamped is not None
    full = pair_fn(_smoke_cfg(arch, total))
    ref = _fact_keys(*full, workers=0)
    assert _fact_keys(*stamped, workers=0) == ref
    assert _fact_keys(*stamped, workers=4) == ref
    assert ref


def test_round_layers_whole_periods():
    cfg = get_config("jamba_1_5_large", smoke=True)
    assert _round_layers(cfg, 5).n_layers == 8  # rounded up to block_period=4


def test_concat_extension_uses_family_extent():
    """A postamble concat mixing a per-period family with an unrelated input
    must grow by the family member's extent, not the last input's."""
    S = LAYER_TAG_STRIDE
    g = Graph()
    x = g.add("input", (), (4,), "float32")
    w = g.add("input", (), (4, 4), "float32")  # unrelated concat operand
    outs = []
    h = x
    for li in range(3):
        h = g.add("tanh", [h], (4,), "float32", layer=li * S)
        outs.append(h)
    rs = [g.add("reshape", [o], (1, 4), "float32", {"new_sizes": (1, 4)})
          for o in outs]
    cat = g.add("concat", rs + [w], (7, 4), "float32", {"dimension": 0})
    g.mark_output(cat)
    sg = stamp_graph(g, 5, lambda t: t // S)
    assert sg is not None
    out = sg[sg.outputs[0]]
    assert len(out.inputs) == 6  # 5 family members + w
    assert out.shape == (9, 4)  # grew by extra_periods * member extent (1)
    assert out.inputs[-1] == w  # unrelated operand untouched


def test_stamp_falls_back_on_irregular_trace():
    """A trace whose periods differ structurally must refuse to stamp."""
    S = LAYER_TAG_STRIDE
    g = Graph()
    x = g.add("input", (), (4,), "float32")
    for li in range(3):
        x = g.add("tanh", [x], (4,), "float32", layer=li * S)
        if li == 2:  # period 2 has an extra node: lengths diverge
            x = g.add("neg", [x], (4,), "float32", layer=li * S)
    g.mark_output(x)
    assert stamp_graph(g, 6, lambda t: t // S) is None

    # fewer traced periods than TRACE_PERIODS must also refuse
    g2 = Graph()
    x = g2.add("input", (), (4,), "float32")
    for li in range(2):
        x = g2.add("tanh", [x], (4,), "float32", layer=li * S)
    g2.mark_output(x)
    assert stamp_graph(g2, 6, lambda t: t // S) is None
