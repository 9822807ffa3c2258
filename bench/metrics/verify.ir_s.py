"""verify.ir_s: seconds per verdict in the verifier's own jaxpr -> IR
converter (``Tracer.trace_jaxpr``: the ``trace.ir`` spans of
``Report.timings.spans``, inclusive), the mean over the traced window's
verdicts.  None where the program records no spans."""


def read(run):
    spans = [getattr(r.timings, "spans", None) for r in run.data.get("reports", ())]
    if not spans or not all(spans):
        return None
    return sum(s.get("trace.ir", {}).get("s", 0.0) for s in spans) / len(spans)
