"""Persistent device-resident decode-loop state.

The per-step engine paths marshal the page table, lengths and pending
tokens from numpy into every decode dispatch — a full table upload and a
host sync per generated token. ``DeviceLoopState`` is the fused paths'
alternative: the four loop arrays live on device as persistent donated
buffers, the engine's host numpy mirrors stay the bookkeeping source of
truth, and the two are reconciled by uploading only the slot rows the
host actually touched since the last horizon (admission, growth, CoW,
slot recycle). After a fused dispatch the device arrays are already
advanced — the engine updates its mirrors by the same arithmetic and
adopts the returned buffers without a download, so steady-state decode
costs one dirty-row upload and one token sync per horizon.

The object also owns the host<->device traffic counters the reports
publish (``device_dispatches``, ``host_syncs``,
``page_table_upload_bytes``); the per-step fallback paths route their
per-dispatch accounting through the same counters so the two paths are
directly comparable in ``bench_serve --scenario decode_wall``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


class DeviceLoopState:
    """Donated device twins of the engine's decode-loop arrays.

    ``table`` (B, M) int32, ``lengths``/``pending``/``remaining`` (B,)
    int32. ``touch(slot)`` marks a slot's mirror row dirty; ``sync``
    uploads every dirty row in ONE jitted dispatch (slot indices are a
    traced vector padded to a power of two, so at most ``log2(B)+1``
    widths ever compile); ``adopt`` takes a fused step's outputs as the
    new device arrays without marking anything dirty — the host mirrors
    were advanced by identical arithmetic.
    """

    def __init__(self, num_slots: int, max_rows: int, sharding=None):
        """``sharding``: where the fused step returns the loop arrays
        (None: uncommitted), so they start where they will come back."""
        self.num_slots = num_slots
        arrays = (jnp.zeros((num_slots, max_rows), jnp.int32),
                  *(jnp.zeros((num_slots,), jnp.int32) for _ in range(3)))
        if sharding is not None:
            arrays = jax.device_put(arrays, sharding)
        self.table, self.lengths, self.pending, self.remaining = arrays
        self._dirty: set[int] = set(range(num_slots))
        self._row_bytes = max_rows * 4
        self._write = jax.jit(self._scatter_rows, donate_argnums=(0, 1, 2, 3))
        self.device_dispatches = 0
        self.host_syncs = 0
        self.page_table_upload_bytes = 0

    @staticmethod
    def _scatter_rows(table, lengths, pending, remaining, idx, rows, ln,
                      pend, rem):
        # duplicate indices (the power-of-two pad repeats the last dirty
        # slot) scatter identical values, so write order cannot matter
        return (table.at[idx].set(rows), lengths.at[idx].set(ln),
                pending.at[idx].set(pend), remaining.at[idx].set(rem))

    def touch(self, slot: int) -> None:
        self._dirty.add(slot)

    def count(self, dispatches: int = 0, syncs: int = 0,
              upload_bytes: int = 0) -> None:
        """Shared traffic ledger for the per-step fallback paths (one
        dispatch + one sync + one full-table upload per decode step)."""
        self.device_dispatches += dispatches
        self.host_syncs += syncs
        self.page_table_upload_bytes += upload_bytes

    def sync(self, page_table: np.ndarray, lengths: np.ndarray,
             pending: np.ndarray, remaining: np.ndarray) -> None:
        """Upload the dirty slots' mirror rows to the device arrays."""
        if not self._dirty:
            return
        idx = sorted(self._dirty)
        self._dirty.clear()
        width = 1
        while width < len(idx):
            width *= 2
        idx += [idx[-1]] * (width - len(idx))
        self.table, self.lengths, self.pending, self.remaining = \
            self._write(self.table, self.lengths, self.pending,
                        self.remaining, jnp.asarray(idx, jnp.int32),
                        jnp.asarray(page_table[idx]),
                        jnp.asarray(lengths[idx]),
                        jnp.asarray(pending[idx]),
                        jnp.asarray(remaining[idx]))
        self.device_dispatches += 1
        self.page_table_upload_bytes += width * self._row_bytes

    def adopt(self, pending, lengths, remaining) -> None:
        """Rebind the donated loop buffers a fused dispatch returned."""
        self.pending, self.lengths, self.remaining = \
            pending, lengths, remaining
