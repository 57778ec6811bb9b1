"""Share of the traced window the engine loop's host code took, in percent:
the union of the program's ``engine.*`` spans less the parts under
``engine.prefill`` (the backend's prefill up to its logits on the host) and
``engine.wait`` (the host blocked on the decode's tokens), over the window.
Read from the spans of ``runtime/tracing.py`` on the trace's host plane, on
the clock of the device lines. Standard error gets each phase's self time
and the part of it in which the device ran no op."""

import sys

from chipbench import program

BLOCKED = ("engine.prefill", "engine.wait")


def compute(run):
    tr = run.trace
    if tr is None or tr.window_ns <= 0:
        return None
    segs = program.self_segments(tr)
    if not segs:
        return None
    own = program.self_ns(segs)
    idle = program.idle_ns(tr, segs)
    for name, ns in own.items():
        gap = "no device ops" if idle is None else f"{idle[name] / 1e9} s"
        print(f"host_loop_share: {name} self {ns / 1e9} s, device idle "
              f"{gap}", file=sys.stderr, flush=True)
    loop = sum(ns for name, ns in own.items() if name not in BLOCKED)
    return 100.0 * loop / tr.window_ns
