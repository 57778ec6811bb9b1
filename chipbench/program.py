"""What the program reports of itself: the engine loop's spans in the
profiler trace, and its counters.

``runtime/tracing.py`` writes spans named ``engine.*`` on the trace's host
plane, on the clock of the device lines, and keeps rings of counter samples
stamped on the host clock of ``Run.open_t`` and ``Run.close_t``. A program
that has neither leaves the span list empty and the counters missing, and
the readers here return None for it.
"""

from __future__ import annotations

import numpy as np

from . import trace as trace_mod

PREFIX = "engine."
PHASES = ("engine.step", "engine.admit", "engine.prefill", "engine.grow",
          "engine.sync", "engine.decode", "engine.wait", "engine.deliver")


def spans(tr, names=None) -> list:
    """The engine's spans, or those named in ``names``."""
    return [e for e in tr.host if e.name.startswith(PREFIX)
            and (names is None or e.name in names)]


def self_segments(tr) -> list[tuple]:
    """(start, end, phase) pieces of the window, each owned by the innermost
    engine span over it. The spans come from one thread, so they nest."""
    out, stack, cur = [], [], None
    for s, e, name in sorted(((max(x.start, tr.t0), min(x.end, tr.t1),
                               x.name) for x in spans(tr)
                              if x.end > tr.t0 and x.start < tr.t1),
                             key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            end, owner = stack.pop()
            if end > cur:
                out.append((cur, end, owner))
                cur = end
        if stack and s > cur:
            out.append((cur, s, stack[-1][1]))
        cur = s if cur is None else max(cur, s)
        stack.append((e, name))
    while stack:
        end, owner = stack.pop()
        if end > cur:
            out.append((cur, end, owner))
            cur = end
    return out


def self_ns(segments) -> dict[str, float]:
    """Self time of each phase."""
    out = dict.fromkeys(PHASES, 0.0)
    for s, e, name in segments:
        out[name] = out.get(name, 0.0) + (e - s)
    return out


def idle_ns(tr, segments) -> dict[str, float] | None:
    """Time of each phase's self time in which no op ran on the device, or
    None if the trace holds no device ops."""
    busy = trace_mod.clip(tr.ops, tr.t0, tr.t1)
    if not busy:
        return None
    merged = []
    for s, e in sorted(busy):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    starts = np.array([m[0] for m in merged])
    ends = np.array([m[1] for m in merged])
    before = np.concatenate([[0.0], np.cumsum(ends - starts)])

    def busy_to(x):
        """Busy time from the window's start to each x."""
        i = np.searchsorted(starts, x, side="right") - 1
        inside = np.where(i >= 0, np.minimum(x, ends[i]) - starts[i], 0.0)
        return np.where(i >= 0, before[i] + inside, 0.0)

    out = dict.fromkeys(PHASES, 0.0)
    if not segments:
        return out
    seg = np.array([(s, e) for s, e, _ in segments])
    idle = (seg[:, 1] - seg[:, 0]) - (busy_to(seg[:, 1]) - busy_to(seg[:, 0]))
    for (_, _, name), v in zip(segments, idle):
        out[name] = out.get(name, 0.0) + float(v)
    return out


def counters():
    """The program's counter rings (``repro.runtime.tracing``), or None for
    a program that keeps none."""
    try:
        from repro.runtime import tracing
    except ImportError:
        return None
    return tracing
