"""95th percentile over requests of the time per output token: for each
request with n >= 2 tokens delivered inside the window, (last - first) /
(n - 1) of their delivery times. The sample count goes to standard error."""

import sys

import numpy as np


def compute(run):
    per = {}
    for r, _, t in run.deliveries():
        per.setdefault(id(r), []).append(t)
    tpot = [(ts[-1] - ts[0]) / (len(ts) - 1) for ts in per.values()
            if len(ts) >= 2]
    print(f"tpot_p95_ms: {len(tpot)} requests with two or more tokens in "
          f"the window; median {1e3 * float(np.median(tpot)) if tpot else 0}"
          f" ms", file=sys.stderr, flush=True)
    if not tpot:
        return None
    return 1e3 * float(np.percentile(tpot, 95))
