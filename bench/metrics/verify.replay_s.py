"""verify.replay_s: seconds per verdict replaying memo-hit layers (facts
moved from the layer memo, then the fusion settle: the ``rules.replay``
spans of ``Report.timings.spans``), the mean over the traced window's
verdicts.  None where the program records no spans."""


def read(run):
    spans = [getattr(r.timings, "spans", None) for r in run.data.get("reports", ())]
    if not spans or not all(spans):
        return None
    return sum(s.get("rules.replay", {}).get("s", 0.0) for s in spans) / len(spans)
