"""Verifier cells: the launch gate, ``Session.verify(arch, plan)``, in a
closed loop of verdicts.

Each verdict is asked about the cell's plan with a fresh ``Session`` and no
disk cache, so it pays trace, stamp, rules and localize.  The mix's
``pattern`` repeats: ``clean`` asks about the plan as the program builds it,
any other entry plants that kind of silent bug (``bench/plant.py``) in a
layer drawn from the seed.  Set-up runs one verdict of each kind in the
pattern and discards them: the first verdict of a process traces about twice
as slowly as later ones.  The window runs verdicts until ``--seconds`` has
passed and the pattern has come round whole, so every run weighs the kinds
alike; the verdict running then is finished and counted.

``correct``: every verdict of the window is checked against the answer the
plant fixes: a clean plan is verified with no bug site; a planted plan is
refuted with at least one bug site, and every bug site lies in the planted
layer.  The number compared is the count of wrong answers, with the limit 0.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from bench import harness
from bench.plant import Plant

PLANTS = 2  # seed stream of the plants' layers and draws


def schedule(run: harness.Run, n: int, layers: int) -> list:
    """The first ``n`` verdicts' plants (None for a clean plan), in layers
    drawn from ``range(layers)``."""
    tr = run.cell.traffic
    rng = np.random.default_rng([run.seed, PLANTS])
    out = []
    for i in range(n):
        kind = tr["pattern"][i % len(tr["pattern"])]
        out.append(None if kind == "clean" else
                   Plant(kind, int(rng.integers(0, layers)), int(rng.integers(0, 2**30))))
    return out


def ask(arch: str, plan, plant, options=None):
    """One verdict; returns (report, the graph it was about)."""
    from repro.verify import Session

    seen = {}

    def mutate(g):
        seen["g"] = plant(g) if plant is not None else g
        return seen["g"]

    with Session(options=options) as s:
        rep = s.verify(arch, plan, mutate_dist=mutate, mutate_pure=True)
    return rep, seen["g"]


def wrong(rep, graph, plant) -> bool:
    """Whether a verdict differs from the answer its plant fixes."""
    if plant is None:
        return not rep.verified or bool(rep.bug_sites)
    if rep.verified or plant.tag is None or not rep.bug_sites:
        return True  # missed, never looked there, or not localized
    return any(graph[b.node].layer != plant.tag for b in rep.bug_sites)


def plan_of(traffic: dict, layers=None):
    from repro.verify import Plan

    kw = dict(traffic["plan"])
    if layers is not None:
        kw["layers"] = layers
    return Plan(**kw)


def run(run: harness.Run, stats: harness.CompileStats) -> dict:
    from repro.core.verifier import VerifyOptions

    tr = run.cell.traffic
    arch = run.cell.config["program"]["arch"]
    size = run.cell.config["rehearse"] if run.rehearse else {}
    plan = plan_of(tr, size.get("layers"))
    kinds = list(dict.fromkeys(tr["pattern"]))
    warm_rng = np.random.default_rng([run.seed, PLANTS, 1])
    for kind in kinds:  # set-up: one discarded verdict of each kind
        ask(arch, plan, None if kind == "clean" else
            Plant(kind, int(warm_rng.integers(0, plan.layers)), 0))
    setup_s = harness.since_start()
    c, h, m = stats.snapshot()
    run.say(f"[setup] {setup_s} s to the window; backend compile {c} s, "
            f"cache hits {h} misses {m}")

    plants = schedule(run, 10_000, plan.layers)
    reps, times, wrongs = [], [], 0
    with harness.traced_window(run):
        t_end = time.perf_counter() + run.seconds
        whole = len(tr["pattern"])
        while time.perf_counter() < t_end or len(times) % whole:
            plant = plants[len(times)]
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("verdict"):
                rep, graph = ask(arch, plan, plant)
            times.append(time.perf_counter() - t0)
            wrongs += wrong(rep, graph, plant)
            reps.append(rep)
    run.say(f"[window] {len(times)} verdicts in {sum(times)} s; wrong answers "
            f"{wrongs}; verified {sum(r.verified for r in reps)}")
    peak = harness.memory_peak(run.devices) if run.devices else 0
    run.data["reports"] = reps

    bd = None
    if run.trace:
        # the rule profiler costs ~15% of the rules phase: one verdict after
        # the window, outside every number, gives the breakdown by family
        prof, _ = ask(arch, plan, None, VerifyOptions(profile=True))
        fam = prof.timings.profile["op_families"]
        n = len(reps)
        phases = [["trace", sum(r.timings.trace_s for r in reps) / n],
                  ["stamp", sum(r.timings.stamp_s for r in reps) / n],
                  ["localize", sum(r.timings.localize_s for r in reps) / n]]
        phases += [[f"rules:{k}", v["time_s"]] for k, v in fam.items()]
        t = run.data.get("trace")
        bd = {"device_ops": [[k, v] for k, v in t.top_ops()] if t else [],
              "idle_gaps": sorted(phases, key=lambda kv: -kv[1])[:10]}
    return {
        "correct": wrongs <= run.cell.limits["wrong_answers"],
        "attempted": len(times),
        "failed": wrongs,
        "memory_peak_bytes": int(peak),
        "end_to_end": {"verify_s": sum(times) / len(times), "setup_s": setup_s},
        "checks": {"wrong_answers": (wrongs, run.cell.limits["wrong_answers"])},
        "breakdown": bd,
    }
