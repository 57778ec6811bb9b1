"""Share of the traced window in which no operation ran on the device:
100 * (1 - union of the device-op intervals / window)."""

from chipbench import trace


def compute(run):
    tr = run.trace
    if tr is None or tr.window_ns <= 0 or not tr.ops:
        return None
    return 100.0 * (1.0 - trace.busy_ns(tr) / tr.window_ns)
