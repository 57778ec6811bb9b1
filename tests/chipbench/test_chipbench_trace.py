"""The reduction from a profiler trace to the per-layer metrics' intervals.

``data/small.xplane.pb.gz`` is a trace recorded on one TPU v5e: a tiny dense
cell (two layers of width 256, two heads of 128, four slots) served by
``runtime.Engine`` for a quarter of a second, through the harness's traced
window. ``data/small.window.json`` holds the window it was reduced over.
The expected numbers were worked out from the raw events with a separate
sweep (every op boundary sorted, counting open intervals), not with the
functions under test.
"""

import gzip
import json
from pathlib import Path

import pytest

from chipbench import bench, trace

DATA = Path(__file__).resolve().parent / "data"
ROOT = bench.checkout_root()


@pytest.fixture(scope="module")
def small(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "small.xplane.pb"
    path.write_bytes(gzip.decompress(
        (DATA / "small.xplane.pb.gz").read_bytes()))
    tr = trace.load(path)
    w = json.loads((DATA / "small.window.json").read_text())
    tr.t0, tr.t1 = w["t0"], w["t1"]
    return tr, w


def test_union_by_hand():
    # (0, 10) and (5, 15) merge to 15 ns; (20, 30) adds 10; (12, 14) is
    # inside the first
    assert trace.union_ns([(20, 30), (0, 10), (5, 15), (12, 14)]) == 25
    ev = [trace.Event(0, 10, "a"), trace.Event(5, 15, "b"),
          trace.Event(20, 30, "c"), trace.Event(40, 50, "d")]
    # clipped to [2, 25]: 2..15 and 20..25
    assert trace.union_ns(trace.clip(ev, 2, 25)) == 18
    tr = trace.DeviceTrace(modules=[], ops=ev, host=[], markers={}, t0=2,
                           t1=25)
    assert trace.busy_ns(tr) == 18
    assert trace.idle_gaps(tr) == [["host", 5e-9]]


def test_small_trace_planes(small):
    tr, w = small
    assert tr.chips == 1
    assert "chipbench.open" in tr.markers
    assert tr.markers["chipbench.open"] == w["markers"]["chipbench.open"]


def test_small_trace_busy_union(small):
    tr, w = small
    assert trace.busy_ns(tr) == pytest.approx(w["expected"]["busy_ns"],
                                              abs=1)


def test_small_trace_launch_count(small):
    tr, w = small
    assert trace.launches(tr) == w["expected"]["launches"]
    assert trace.launches(tr, "decode_multi") == \
        w["expected"]["decode_launches"]


def test_small_trace_kernel_time(small):
    tr, w = small
    kernel = bench.load_metric(ROOT, "paged_attn_roofline").KERNEL
    ns, calls = trace.op_ns(tr, kernel)
    assert calls == w["expected"]["kernel_calls"]
    assert ns == pytest.approx(w["expected"]["kernel_ns"], abs=1)


def test_small_trace_prefill_share(small):
    tr, w = small
    programs = bench.load_metric(ROOT, "prefill_device_share").PREFILL_PROGRAMS
    ns = trace.module_ns(tr, programs)
    assert ns == pytest.approx(w["expected"]["prefill_ns"], abs=1)
