"""ssd_scan_roofline: the SSD scan kernel's share of its roofline, in %.

The least time the chip could take for the kernel's calls that lie wholly
inside the traced window (the larger of operations over the bf16 peak and
bytes over the HBM bandwidth, from ``bench/flops.py``) over the summed
device time of those calls in the trace.  The bound that sets the least
time is printed on standard error.  None when the trace holds no such
call."""
import sys

from bench.flops import roofline_s, ssd_scan_cost
from bench.peaks import peaks

NAME = "ssd_scan"  # the kernel's pallas_call name, as the trace shows it


def read(run):
    t = run.data.get("trace")
    if t is None or "ssd_scan" not in run.data or not run.devices:
        return None
    calls, kernel_s = t.kernel_calls(NAME)
    if calls <= 0 or kernel_s <= 0:
        return None
    shape = run.data["ssd_scan"]
    flops, nbytes = ssd_scan_cost(**shape)
    pk = peaks(run.devices[0].device_kind)
    least, bound = roofline_s(calls * flops, calls * nbytes, pk["bf16_flops"],
                              pk["hbm_bytes_per_s"])
    print(f"[ssd_scan_roofline] {calls} calls, {kernel_s} s in the kernel, "
          f"{bound} bound", file=sys.stderr)
    return 100.0 * least / kernel_s
