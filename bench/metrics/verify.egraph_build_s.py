"""verify.egraph_build_s: seconds per verdict building the rules phase's
state (``Propagator`` and its FusionTier e-graph, the worklist engine, the
input facts: the ``rules.egraph_build`` spans of ``Report.timings.spans``),
the mean over the traced window's verdicts.  None where the program records
no spans."""


def read(run):
    spans = [getattr(r.timings, "spans", None) for r in run.data.get("reports", ())]
    if not spans or not all(spans):
        return None
    return sum(s.get("rules.egraph_build", {}).get("s", 0.0)
               for s in spans) / len(spans)
