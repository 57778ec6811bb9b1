"""Roofline share of the paged decode-attention kernel, in percent: the
least time the chip needs for the attention work the traffic asked for,
over the kernel's summed device time in the traced window.

The work is one decode token per layer over its live context: for token
i > 0 of a request with a prompt of p tokens, p + i cache rows, whose K and
V are read once in bfloat16, with q.k and p.v over every head. The roofline
time is max(FLOPs / bf16 peak, bytes / HBM bandwidth); which of the two
bounds it goes to standard error. The kernel is matched by the name it has
in today's trace: its custom-call op is named after the jitted wrapper,
``%paged_decode_attention.<n> = bf16[B,KV,G,dh] custom-call(...)``."""

import sys

from chipbench import peaks, trace

KERNEL = r"^%paged_decode_attention\.\d+ = "


def compute(run):
    tr = run.trace
    if tr is None:
        return None
    kernel_ns, calls = trace.op_ns(tr, KERNEL)
    if kernel_ns <= 0:
        return None
    m = run.model
    flops = nbytes = 0.0
    for r, i, _ in run.deliveries():
        if i == 0:
            continue
        f, b = peaks.paged_attn_work(m, len(r.prompt) + i)
        flops += f * m["num_layers"]
        nbytes += b * m["num_layers"]
    t, bound = peaks.roofline_s(flops, nbytes, run.peaks)
    print(f"paged_attn_roofline: {calls} kernel calls, {kernel_ns / 1e9} s;"
          f" bound by {bound}", file=sys.stderr, flush=True)
    return 100.0 * t / (kernel_ns / 1e9)
