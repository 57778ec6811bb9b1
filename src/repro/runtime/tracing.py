"""Spans and counters of the engine loop.

``Engine.run`` writes its spans as ``jax.profiler`` trace annotations:
while a profiler runs they land on its host plane, on the clock of the
device lines, so a reader can lay what the host did beside what the chip
did. With no profiler running a span costs about a microsecond, and its
keyword arguments are not formatted. The spans:

  engine.step     one loop iteration (a step trace event, ``step_num``)
  engine.admit    one request's admission: scheduler pick, pages, prefill,
                  first-token sample and delivery (``rid``)
  engine.prefill  inside engine.admit: the backend's prefill, up to its
                  logits on the host
  engine.grow     page growth, copy-on-write and preemption
  engine.sync     the upload of the loop state's dirty rows
  engine.decode   the decode dispatch, fused or per-step
  engine.wait     the host blocked on the dispatch's tokens or logits
  engine.deliver  handing the tokens to requests, finishing the done ones

Counters are kept whether or not a profiler runs, in bounded rings of
samples stamped with ``time.monotonic()``: ``DISPATCHES`` holds one sample
per decode dispatch (``h`` steps run, ``live`` slots, the ``cause`` that
bound ``h``, one of ``CAUSES``), ``PREFILLS`` one per prefill (``prompt``
tokens, ``computed`` tokens after bucket padding). The rings belong to the
process, as the profiler's buffer does, so a reader that never holds the
engine finds them; it takes the samples of its own time window.
"""

from __future__ import annotations

import collections
import math
import time

CAPACITY = 1 << 16

# what bound a decode dispatch's horizon: the horizon cap, a slot's next
# page boundary, a request's last token, a free slot with work queued
# (admission retried every step), the next arrival; ``unfused`` is the
# per-step path
CAUSES = ("cap", "page", "finish", "admit", "arrival", "unfused")


class Ring:
    """The newest ``capacity`` samples of one counter."""

    def __init__(self, fields: tuple[str, ...], capacity: int = CAPACITY):
        self.Sample = collections.namedtuple("Sample", ("t",) + fields)
        self._buf: collections.deque = collections.deque(maxlen=capacity)
        self._lost_t = -math.inf       # stamp of the newest dropped sample

    def add(self, *values) -> None:
        if len(self._buf) == self._buf.maxlen:
            self._lost_t = self._buf[0].t
        self._buf.append(self.Sample(time.monotonic(), *values))

    def window(self, t0: float, t1: float) -> list | None:
        """Samples stamped in (t0, t1], or None if the ring has dropped
        any of them."""
        if self._lost_t > t0:
            return None
        return [s for s in self._buf if t0 < s.t <= t1]


DISPATCHES = Ring(("h", "live", "cause"))
PREFILLS = Ring(("prompt", "computed"))
