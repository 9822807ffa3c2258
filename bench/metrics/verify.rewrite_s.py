"""verify.rewrite_s: seconds per verdict deriving facts by rule: the layers
the memo missed and the cross-layer cleanup (the ``rules.rewrite`` and
``rules.cleanup`` spans of ``Report.timings.spans``), the mean over the
traced window's verdicts.  None where the program records no spans."""


def read(run):
    spans = [getattr(r.timings, "spans", None) for r in run.data.get("reports", ())]
    if not spans or not all(spans):
        return None
    return sum(s.get(k, {}).get("s", 0.0) for s in spans
               for k in ("rules.rewrite", "rules.cleanup")) / len(spans)
