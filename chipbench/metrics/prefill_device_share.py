"""Share of the traced window the device spent in prefill programs, in
percent: the paged backend's prefill-and-page-write program, and the
recurrent backend's prefill and the write of its state into a slot.

Matched by today's program names in the trace: ``jit_prefill_write(...)``,
``jit__write_slot(...)``, and ``jit__lambda(...)``, the recurrent backend's
prefill, which it jits as a lambda. That last name is not the prefill's
alone: the recurrent backend's per-step decode (``RecurrentBackend._decode``,
called only when the fused horizon is off, i.e. sampled decoding or horizon
1) and the pool's staging copy (``DeviceDmaChannel``, pooled engine only)
are lambdas too. Every recurrent prefill is followed by one slot write, so
the reading is given only while the window's ``jit__lambda`` launches and
slot writes pair up (one apart at most, at the window's edges); otherwise
the pattern has caught another program and the metric reads nothing."""

import sys

from chipbench import trace

PREFILL_PROGRAMS = r"^jit_(prefill_write|_lambda|_write_slot)\("
RECURRENT_PREFILL = r"^jit__lambda\("
SLOT_WRITE = r"^jit__write_slot\("


def compute(run):
    tr = run.trace
    if tr is None or tr.window_ns <= 0:
        return None
    lam = trace.launches(tr, RECURRENT_PREFILL)
    writes = trace.launches(tr, SLOT_WRITE)
    if abs(lam - writes) > 1:
        print(f"prefill_device_share: {lam} jit__lambda launches against "
              f"{writes} slot writes; not only prefills match",
              file=sys.stderr, flush=True)
        return None
    return 100.0 * trace.module_ns(tr, PREFILL_PROGRAMS) / tr.window_ns
