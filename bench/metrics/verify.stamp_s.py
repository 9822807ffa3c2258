"""verify.stamp_s: seconds per verdict in the verifier's stamp phase
(``Report.timings.stamp_s``), the mean over the traced window's verdicts."""


def read(run):
    reps = run.data.get("reports")
    if not reps:
        return None
    return sum(r.timings.stamp_s for r in reps) / len(reps)
