"""Share of the latent cache rows that the window's decode steps gathered
which were live, in percent: the rows the live slots attend over (each
slot's context and its new token, summed over steps) over the rows the
steps gather (every slot times the page table's width in rows), per
layer, summed over the window's decode dispatches, from the program's
latent read counter (``LATENT_READS`` in ``runtime/tracing.py``, one
sample per dispatch stamped on the host clock of the window). None if the
program keeps no such counter or its ring dropped samples of the window.
Standard error gets the sums."""

import sys

from chipbench import program


def compute(run):
    c = program.counters()
    ring = None if c is None else getattr(c, "LATENT_READS", None)
    got = None if ring is None else ring.window(run.open_t, run.close_t)
    if not got:
        return None
    live = sum(s.live for s in got)
    gathered = sum(s.gathered for s in got)
    print(f"latent_read_live_share: {len(got)} dispatches, {live} live "
          f"latent rows of {gathered} gathered, per layer", file=sys.stderr,
          flush=True)
    return 100.0 * live / gathered
