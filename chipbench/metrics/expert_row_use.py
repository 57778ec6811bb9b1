"""Share of the expert rows that the window's decode steps computed which
held a live slot's routed claim, in percent: the claims on experts held
here over the expert rows computed for them, summed over the window's
decode dispatches, from the program's route counter (``ROUTES`` in
``runtime/tracing.py``, one sample per dispatch stamped on the host clock
of the window). Dropless decode runs every held expert at a capacity of
every slot, so this reads how much of the expert FFN's work is claimed.
None if the program keeps no such counter or its ring dropped samples of
the window. Standard error gets the sums and the mean claims per held
expert and step."""

import sys

from chipbench import program


def compute(run):
    c = program.counters()
    ring = None if c is None else getattr(c, "ROUTES", None)
    got = None if ring is None else ring.window(run.open_t, run.close_t)
    if not got:
        return None
    claims = sum(s.claims for s in got)
    rows = sum(s.rows for s in got)
    print(f"expert_row_use: {len(got)} dispatches, {claims} claims on held "
          f"experts in {rows} expert rows computed", file=sys.stderr,
          flush=True)
    return 100.0 * claims / rows
