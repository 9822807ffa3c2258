"""verify.localize_s: seconds per verdict in the verifier's localize phase
(``Report.timings.localize_s``), the mean over the traced window's verdicts."""


def read(run):
    reps = run.data.get("reports")
    if not reps:
        return None
    return sum(r.timings.localize_s for r in reps) / len(reps)
