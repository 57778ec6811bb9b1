"""Reduce a JAX profiler trace (``.xplane.pb``) to the intervals the per-layer
metrics read.

On a TPU the profiler writes one plane per chip, ``/device:TPU:<n>``. Its
line ``XLA Modules`` holds one event per program launch, named after the
jitted function and a hash (``jit_decode_multi(9712019879083911307)``), and
its line ``XLA Ops`` one event per operation, named by the HLO instruction's
text (``%paged_decode_attention.5 = bf16[...] custom-call(...)`` for the
Pallas kernel). A loop's ``%while`` op spans the ops of its body, so op
times are summed per instruction but busy time is a union. Asynchronous
copies (line ``Async XLA Ops``) overlap the ops and are not counted as
busy. Host planes hold the runtime's spans and the benchmark's markers
(``chipbench.*``), on the same clock.

Every reduction is clipped to a window [t0, t1] of that clock.
"""

from __future__ import annotations

import dataclasses
import re
from pathlib import Path

MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MARKER = "chipbench."


@dataclasses.dataclass
class Event:
    start: float            # ns, trace clock
    end: float
    name: str


@dataclasses.dataclass
class DeviceTrace:
    modules: list[Event]    # program launches on the device
    ops: list[Event]        # operations on the device
    host: list[Event]       # host spans (runtime and benchmark markers)
    markers: dict[str, float]
    t0: float = 0.0
    t1: float = 0.0
    chips: int = 1

    @property
    def window_ns(self) -> float:
        return self.t1 - self.t0


def load(path: str | Path) -> DeviceTrace:
    """Read a trace; ops and modules of every TPU plane, host spans of
    every host plane."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    modules, ops, host, markers, chips = [], [], [], {}, 0
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            chips += 1
            for line in plane.lines:
                if line.name not in (MODULES_LINE, OPS_LINE):
                    continue
                dst = modules if line.name == MODULES_LINE else ops
                for ev in line.events:
                    dst.append(Event(ev.start_ns, ev.end_ns, ev.name))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(MARKER):
                        markers.setdefault(ev.name, ev.start_ns)
                    if ev.duration_ns > 0:
                        host.append(Event(ev.start_ns, ev.end_ns, ev.name))
    return DeviceTrace(modules, ops, host, markers, chips=max(chips, 1))


def clip(events: list[Event], t0: float, t1: float) -> list[tuple]:
    """(start, end) of each event's part inside [t0, t1]."""
    return [(max(e.start, t0), min(e.end, t1)) for e in events
            if e.end > t0 and e.start < t1]


def union_ns(intervals: list[tuple]) -> float:
    """Length of the union of (start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def busy_ns(tr: DeviceTrace) -> float:
    """Time in which some operation ran on the device, per chip."""
    return union_ns(clip(tr.ops, tr.t0, tr.t1)) / tr.chips


def launches(tr: DeviceTrace, pattern: str = "") -> int:
    """Program launches that start inside the window whose name matches."""
    rx = re.compile(pattern)
    return sum(1 for e in tr.modules
               if tr.t0 <= e.start < tr.t1 and rx.search(e.name))


def module_ns(tr: DeviceTrace, pattern: str) -> float:
    """Device time of the launches whose program name matches ``pattern``."""
    rx = re.compile(pattern)
    return union_ns(clip([e for e in tr.modules if rx.search(e.name)],
                         tr.t0, tr.t1)) / tr.chips


def op_ns(tr: DeviceTrace, pattern: str) -> tuple[float, int]:
    """Summed device time, and count, of the ops whose name matches
    ``pattern``."""
    rx = re.compile(pattern)
    hits = [e for e in tr.ops if rx.search(e.name)]
    spans = clip(hits, tr.t0, tr.t1)
    return sum(e - s for s, e in spans) / tr.chips, len(spans)


_HLO = re.compile(r"^(%[\w.\-]+) = (.*?) ([\w\-]+)\(")
CONTAINERS = ("while", "conditional", "call")


def op_label(name: str) -> tuple[str, str]:
    """(label, kind) of an op: ``%convert.29 convert bf16[2,512,256]`` from
    its HLO text, without layouts."""
    m = _HLO.match(name)
    if not m:
        return name[:120], ""
    ident, typ, kind = m.groups()
    return f"{ident} {kind} {re.sub(r'{[^}]*}', '', typ)[:80]}", kind


def top_ops(tr: DeviceTrace, n: int = 10) -> list[list]:
    """The ``n`` ops with the most device time, [[label, seconds]]; loop and
    call ops, which span their bodies' ops, are left out."""
    tot: dict[str, float] = {}
    for e in tr.ops:
        s, t = max(e.start, tr.t0), min(e.end, tr.t1)
        if t > s:
            key, kind = op_label(e.name)
            if kind in CONTAINERS:
                continue
            tot[key] = tot.get(key, 0.0) + (t - s)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9 / tr.chips] for k, v in best]


def idle_gaps(tr: DeviceTrace, n: int = 10) -> list[list]:
    """The ``n`` longest gaps in which no op ran, each named by the host
    span that overlaps it most among those not much longer than it."""
    busy = sorted(clip(tr.ops, tr.t0, tr.t1))
    gaps, cur = [], tr.t0
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if tr.t1 > cur:
        gaps.append((cur, tr.t1))
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:n]
    out = []
    for gs, ge in gaps:
        best, name = 0.0, "host"
        for h in tr.host:
            if h.name.startswith(MARKER) or h.end - h.start > 10 * (ge - gs):
                continue
            ov = min(h.end, ge) - max(h.start, gs)
            if ov > best:
                best, name = ov, h.name
        out.append([name, (ge - gs) / 1e9])
    return out
