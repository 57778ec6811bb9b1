"""Mean decode steps per decode dispatch in the window: the fused horizon's
length ``h``, from the program's dispatch counter (``runtime/tracing.py``,
one sample per dispatch stamped on the host clock of the window). None if
the program keeps no such counter or its ring dropped samples of the
window. Standard error gets the share of dispatches cut by each cause, the
mean live slots, and the decode tokens the samples account for (sum of
h x live) beside those delivered in the window."""

import sys

from chipbench import program


def compute(run):
    c = program.counters()
    got = None if c is None else c.DISPATCHES.window(run.open_t, run.close_t)
    if not got:
        return None
    n = len(got)
    shares = {k: 100.0 * sum(s.cause == k for s in got) / n
              for k in c.CAUSES}
    delivered = sum(1 for _, i, _ in run.deliveries() if i > 0)
    print(f"decode_steps_per_dispatch: {n} dispatches, cut by "
          + ", ".join(f"{k} {v}%" for k, v in shares.items())
          + f"; mean live slots {sum(s.live for s in got) / n}; "
          f"sum h x live {sum(s.h * s.live for s in got)} against "
          f"{delivered} decode tokens delivered", file=sys.stderr,
          flush=True)
    return sum(s.h for s in got) / n
