"""The verifier's span tree (repro.core.spans): nesting and self time, the
phases it reads into Report.timings, the GC counter, the JSON round trip,
the sum over a plan's scenarios, and the spans on the profiler's clock."""
import gc
import glob
import json
import multiprocessing
import threading
import time

import jax
import pytest

from repro.core import spans
from repro.core.report import PhaseTimings, Report
from repro.verify import Plan, Session
from repro.verify import session as session_mod

ARCH = "llama3_8b"
RULES_CHILDREN = ("rules.egraph_build", "rules.partition", "rules.fingerprint",
                  "rules.replay", "rules.rewrite", "rules.cleanup")


@pytest.fixture(scope="module")
def tp2():
    """A tiny tp-2 verdict whose middle layers replay from the memo."""
    with Session() as s:
        return s.verify(ARCH, Plan(tp=2, layers=4, smoke=True))


def test_nesting_and_self_time():
    with spans.verdict() as rec:
        mark = rec.mark()
        with spans.span("a"):
            time.sleep(0.02)
            with spans.span("b"):
                time.sleep(0.03)
            with spans.span("b"):
                time.sleep(0.01)
        with spans.span("c"):
            time.sleep(0.01)
        t = rec.timings(mark)
    a, b, c = t.spans["a"], t.spans["b"], t.spans["c"]
    assert (a["calls"], b["calls"], c["calls"]) == (1, 2, 1)
    assert b["s"] >= 0.04 and b["self_s"] == pytest.approx(b["s"])
    assert a["s"] >= 0.06
    assert a["self_s"] == pytest.approx(a["s"] - b["s"], abs=1e-9)
    assert 0.02 <= a["self_s"] < a["s"]
    assert [s.name for s in rec.spans] == ["verdict", "a", "b", "b", "c"]
    assert rec.spans[2].parent is rec.spans[1]
    assert rec.spans[1].parent is rec.root
    assert rec.root.seconds >= a["s"] + c["s"]


def _open_a_span():
    with spans.span("w"):
        pass


def _child_records_nothing(rec, out):
    out.put(getattr(spans._tls, "rec", None) is None
            and rec.on_gc not in gc.callbacks)


def test_spans_record_nothing_outside_a_verdict_or_off_its_thread():
    with spans.span("loose"):
        pass
    seen = []
    with spans.verdict() as rec:
        with spans.verdict() as inner:  # joins the open verdict
            seen.append(inner is rec)
        worker = threading.Thread(target=_open_a_span)
        worker.start()
        worker.join()
    assert seen == [True]
    assert [s.name for s in rec.spans] == ["verdict"]


def test_a_process_forked_inside_a_verdict_records_nothing():
    ctx = multiprocessing.get_context("fork")
    out = ctx.Queue()
    with spans.verdict() as rec:
        child = ctx.Process(target=_child_records_nothing, args=(rec, out))
        child.start()
        got = out.get(timeout=60)
        child.join(timeout=60)
    assert got is True and child.exitcode == 0


def test_phases_of_a_tp2_verdict(tp2):
    t = tp2.timings
    assert tp2.verified
    for phase in ("trace", "stamp", "rules", "localize"):
        assert t.spans[phase]["calls"] >= 1
        assert getattr(t, f"{phase}_s") == t.spans[phase]["s"] > 0
    assert t.spans["rules.replay"]["calls"] >= 1
    assert t.spans["rules.rewrite"]["calls"] >= 1
    kids = sum(t.spans[k]["s"] for k in RULES_CHILDREN if k in t.spans)
    assert kids == pytest.approx(t.rules_s, rel=0.02)
    assert t.spans["trace.jaxpr"]["s"] + t.spans["trace.ir"]["s"] <= t.trace_s
    assert t.total_s <= tp2.elapsed_s


def test_warm_verdict_records_no_trace_or_stamp_span():
    with Session() as s:
        plan = Plan(tp=2, layers=2, smoke=True)
        s.verify(ARCH, plan)
        warm = s.verify(ARCH, plan)
    assert "trace" not in warm.timings.spans
    assert "stamp" not in warm.timings.spans
    assert warm.timings.rules_s > 0


def test_a_forced_collection_is_counted_and_the_hook_removed():
    hooks = list(gc.callbacks)
    with spans.verdict() as rec:
        mark = rec.mark()
        with spans.span("work"):
            gc.collect()
        assert rec.on_gc in gc.callbacks
        t = rec.timings(mark)
    assert gc.callbacks == hooks
    assert t.gc_collections[2] >= 1
    assert t.gc_s > 0 and t.gc_s == t.spans["gc"]["s"]
    pause = next(s for s in rec.spans if s.name == "gc")
    assert pause.parent.name == "work" and pause.generation == 2
    # durations stay wall time: the pause lies inside the span it hit
    assert t.spans["work"]["s"] >= t.gc_s
    assert t.spans["work"]["self_s"] == pytest.approx(
        t.spans["work"]["s"] - t.gc_s, abs=1e-9)


def test_a_verdict_counts_its_collections(tp2):
    t = tp2.timings
    assert sum(t.gc_collections) == t.spans.get("gc", {}).get("calls", 0)
    assert 0 <= t.gc_s < tp2.elapsed_s


def test_report_json_round_trip_with_and_without_spans(tp2):
    back = Report.from_json(tp2.to_json())
    assert back.timings == tp2.timings
    assert back.timings.spans["rules.replay"] == tp2.timings.spans["rules.replay"]
    d = json.loads(tp2.to_json())
    for key in ("spans", "gc_s", "gc_collections"):
        del d["timings"][key]
    old = Report.from_json(json.dumps(d))
    assert old.timings.rules_s == tp2.timings.rules_s
    assert old.timings.spans == {} and old.timings.gc_s == 0.0
    assert old.timings.gc_collections == [0, 0, 0]


def test_a_plans_aggregate_is_the_sum_of_its_scenarios(monkeypatch):
    seen = {}
    merge = session_mod._merge

    def spy(arch, plan, results):
        seen["reps"] = [r for _, r in results]
        return merge(arch, plan, results)

    monkeypatch.setattr(session_mod, "_merge", spy)
    with Session() as s:
        rep = s.verify(ARCH, Plan(tp=2, dp=2, layers=2, smoke=True))
    parts = [r.timings for r in seen["reps"]]
    assert len(parts) == 2 and all(p.spans for p in parts)
    t = rep.timings
    for name in set().union(*(p.spans for p in parts)):
        for k in ("calls", "s", "self_s"):
            assert t.spans[name][k] == pytest.approx(
                sum(p.spans.get(name, {}).get(k, 0) for p in parts))
    for phase in ("trace_s", "stamp_s", "rules_s", "localize_s", "gc_s"):
        assert getattr(t, phase) == pytest.approx(
            sum(getattr(p, phase) for p in parts))
    assert t.gc_collections == [sum(g) for g in
                                zip(*(p.gc_collections for p in parts))]
    assert rep.elapsed_s >= sum(r.elapsed_s for r in seen["reps"])
    assert PhaseTimings.merge(parts) == t


def test_spans_land_on_the_profilers_host_plane(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        with Session() as s:
            s.verify(ARCH, Plan(tp=2, layers=4, smoke=True))
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    prof = jax.profiler.ProfileData.from_file(path)
    names = {e.name for p in prof.planes if p.name.startswith("/host:")
             for line in p.lines for e in line.events}
    assert {"verdict", "trace", "trace.ir", "rules", "rules.replay",
            "localize"} <= names
