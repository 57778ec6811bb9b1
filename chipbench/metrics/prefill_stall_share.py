"""Share of the traced window the engine spent in prefill, in percent: the
union of the program's ``engine.prefill`` spans (the backend's prefill call
up to its logits on the host, inside one admission) over the window. Decode
waits for it, so it stalls every live slot. Read from the spans of
``runtime/tracing.py``; standard error gets the padding share of the
prefill tokens computed in the window, from the program's prefill counter."""

import sys

from chipbench import program, trace


def compute(run):
    tr = run.trace
    if tr is None or tr.window_ns <= 0:
        return None
    hits = program.spans(tr, ("engine.prefill",))
    if not program.spans(tr):
        return None
    c = program.counters()
    got = None if c is None else c.PREFILLS.window(run.open_t, run.close_t)
    if got:
        computed = sum(s.computed for s in got)
        pad = 100.0 * (1 - sum(s.prompt for s in got) / computed)
        print(f"prefill_stall_share: {len(got)} prefills, {computed} "
              f"computed tokens, padding {pad}%", file=sys.stderr,
              flush=True)
    return 100.0 * trace.union_ns(trace.clip(hits, tr.t0, tr.t1)) \
        / tr.window_ns
