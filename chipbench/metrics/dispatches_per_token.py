"""Device program launches (every jitted program: prefill, fused decode,
loop-state uploads, slot writes) that start in the traced window, per
generated token delivered in it."""

from chipbench import trace


def compute(run):
    if run.trace is None:
        return None
    n = sum(1 for _ in run.deliveries())
    if n == 0:
        return None
    return trace.launches(run.trace) / n
