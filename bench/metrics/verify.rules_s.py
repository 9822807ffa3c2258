"""verify.rules_s: seconds per verdict in the verifier's rules phase
(``Report.timings.rules_s``), the mean over the traced window's verdicts."""


def read(run):
    reps = run.data.get("reports")
    if not reps:
        return None
    return sum(r.timings.rules_s for r in reps) / len(reps)
