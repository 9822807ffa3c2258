"""verify.gc_s: seconds per verdict the garbage collector paused the
verifier (``Report.timings.gc_s``, counted by a ``gc.callbacks`` hook while
the verdict runs), the mean over the traced window's verdicts.  None where
the program does not count collections."""


def read(run):
    gcs = [getattr(r.timings, "gc_s", None) for r in run.data.get("reports", ())]
    if not gcs or None in gcs:
        return None
    return sum(gcs) / len(gcs)
