"""verify.trace_s: seconds per verdict in the verifier's trace phase
(``Report.timings.trace_s``), the mean over the traced window's verdicts."""


def read(run):
    reps = run.data.get("reports")
    if not reps:
        return None
    return sum(r.timings.trace_s for r in reps) / len(reps)
