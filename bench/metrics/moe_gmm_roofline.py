"""moe_gmm_roofline: the grouped expert matmuls' share of their roofline, in %.

Every call of the ``moe_gmm`` kernel (the forward's gate, up and down
products and the backward's input gradients) and of ``moe_tgmm`` (the
backward's weight gradients) that lies wholly inside the traced window
multiplies one chip's share of a microbatch's routed rows, R = microbatch
tokens x experts per token / chips (exact on average: nothing is dropped),
with one of its expert matrices (D x F).  Each such call needs 2 R D F
operations and moves R (D + F) activations and its experts' E_loc D F
weights once, in bfloat16.  The least time of as many calls (the larger of
operations over the bf16 peak and bytes over the HBM bandwidth) over their
summed device time.  None when the trace holds no such call."""
import sys

from bench.flops import matmul_flops, roofline_s
from bench.peaks import peaks

NAMES = ("moe_gmm", "moe_tgmm")  # the kernels' pallas_call names
BYTES = 2  # bfloat16


def call_cost(tokens: int, top_k: int, chips: int, experts: int, d: int, f: int):
    """(flops, bytes) of one grouped-matmul call on one chip."""
    rows = tokens * top_k / chips
    return (matmul_flops(rows, d, f),
            BYTES * (rows * (d + f) + experts / chips * d * f))


def read(run):
    t = run.data.get("trace")
    if t is None or not run.devices:
        return None
    calls = kernel_s = 0.0
    for name in NAMES:
        c, s = t.kernel_calls(name)
        calls, kernel_s = calls + c, kernel_s + s
    if calls <= 0 or kernel_s <= 0:
        return None
    conf, tr = run.cell.config, run.cell.traffic
    flops, nbytes = call_cost(tr["seq"] * tr["batch"] // tr["microbatches"],
                              conf["num_experts_per_tok"], tr["tp"],
                              conf["num_local_experts"], conf["hidden_size"],
                              conf["intermediate_size"])
    pk = peaks(run.devices[0].device_kind)
    least, bound = roofline_s(calls * flops, calls * nbytes, pk["bf16_flops"],
                              pk["hbm_bytes_per_s"])
    print(f"[moe_gmm_roofline] {calls} calls, {kernel_s} s in the kernels, "
          f"{bound} bound", file=sys.stderr)
    return 100.0 * least / kernel_s
