"""train.mfu: the whole train step's share of the chips' bf16 peak, in %.

The published model's forward and backward matrix-product operations per
token (the configuration's ``train_flops_per_token``: no recomputation, no
padding) times the tokens per second of the steps that complete after the
trace has been read, over the chips times the peak of ``bench/peaks.py``.
None when no two steps complete after it."""
from bench.peaks import peaks


def read(run):
    d = run.data
    if not d.get("tokens_per_s") or not run.devices:
        return None
    peak = peaks(run.devices[0].device_kind)["bf16_flops"]
    return 100.0 * d["tokens_per_s"] * d["flops_per_token"] / (d["chips"] * peak)
