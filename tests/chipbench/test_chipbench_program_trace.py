"""The per-layer metrics that read the program's own spans and counters
(``chipbench/program.py``): the innermost-span attribution and the device
idle time of each phase on a made-up trace, no reading from a program that
writes neither, and a traced run of the tiny cells that reads all three as
numbers in range."""

import json
import time
import types

import pytest

from chipbench import bench, program
from chipbench.trace import DeviceTrace, Event
from tinycell import DEVICE, TEST_PEAKS, make_root

METRICS = ("host_loop_share", "decode_steps_per_dispatch",
           "prefill_stall_share")


def _trace(host, ops=(), t0=0.0, t1=100.0):
    return DeviceTrace(modules=[], ops=[Event(*o, "op") for o in ops],
                       host=[Event(s, e, n) for s, e, n in host],
                       markers={}, t0=t0, t1=t1)


# one step: admission with a prefill inside it, then decode and wait
NESTED = [(10, 90, "engine.step"), (12, 40, "engine.admit"),
          (15, 35, "engine.prefill"), (50, 55, "engine.decode"),
          (55, 80, "engine.wait"), (95, 99, "other")]


def test_each_instant_goes_to_the_innermost_span():
    tr = _trace(NESTED)
    own = program.self_ns(program.self_segments(tr))
    assert own["engine.step"] == (12 - 10) + (50 - 40) + (90 - 80)
    assert own["engine.admit"] == (15 - 12) + (40 - 35)
    assert own["engine.prefill"] == 20
    assert (own["engine.decode"], own["engine.wait"]) == (5, 25)
    assert own["engine.grow"] == 0
    assert sum(own.values()) == 80          # the step's length, once


def test_spans_are_clipped_to_the_window():
    tr = _trace(NESTED, t0=20.0, t1=60.0)
    own = program.self_ns(program.self_segments(tr))
    assert own["engine.prefill"] == 15
    assert own["engine.admit"] == 5
    assert own["engine.wait"] == 5
    assert sum(own.values()) == 40


def test_idle_time_is_split_by_phase():
    # the device runs 20-30 (under the prefill) and 52-70 (decode, wait)
    tr = _trace(NESTED, ops=[(20, 25), (24, 30), (52, 70)])
    segs = program.self_segments(tr)
    idle = program.idle_ns(tr, segs)
    assert idle["engine.prefill"] == 20 - 10
    assert idle["engine.decode"] == 2
    assert idle["engine.wait"] == 25 - 15
    assert idle["engine.step"] == 22
    assert program.idle_ns(_trace(NESTED), segs) is None   # no ops


def test_a_program_without_spans_or_counters_reads_nothing(tmp_path,
                                                          monkeypatch):
    root = make_root(tmp_path)
    run = types.SimpleNamespace(trace=_trace([(10, 90, "other")]),
                                open_t=0.0, close_t=1.0, requests=[],
                                deliveries=lambda: iter(()))
    monkeypatch.setattr(program, "counters", lambda: None)
    for name in METRICS:
        assert bench.load_metric(root, name).compute(run) is None


def test_a_window_the_ring_lost_reads_nothing(tmp_path, monkeypatch):
    from repro.runtime import tracing
    root = make_root(tmp_path)
    small = tracing.Ring(("h", "live", "cause"), capacity=2)
    monkeypatch.setattr(tracing, "DISPATCHES", small)
    t0 = time.monotonic()
    for _ in range(3):
        small.add(4, 2, "page")
    run = types.SimpleNamespace(open_t=t0, close_t=time.monotonic(),
                                deliveries=lambda: iter(()))
    metric = bench.load_metric(root, "decode_steps_per_dispatch")
    assert metric.compute(run) is None
    run.open_t = small._lost_t          # from the newest dropped sample on
    assert metric.compute(run) == 4.0


@pytest.fixture
def root(tmp_path, monkeypatch):
    from repro.launch import compile_cache
    monkeypatch.setattr(compile_cache, "use_compile_cache", lambda: "off")
    root = make_root(tmp_path)
    bm = json.loads((root / "BENCHMARK.json").read_text())
    for m in bm["per_layer"]:
        if m["name"] in METRICS:
            m["workloads"] += ["tiny.tinychat", "tinyrwkv.tinychat"]
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    return root


@pytest.mark.parametrize("cell", ["tiny.tinychat", "tinyrwkv.tinychat"])
def test_traced_run_reads_the_program_metrics(root, monkeypatch, cell):
    monkeypatch.setattr(bench, "TRACE_SECONDS", 1.0)
    out = bench.run_cell(root, cell, 3, 30.0, True,
                         t_start=time.monotonic(), device=DEVICE,
                         pk=TEST_PEAKS, log=lambda *_: None)
    assert out["correct"]
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(METRICS) <= set(m)
    assert 0 < m["host_loop_share"] < 100
    assert 0 < m["prefill_stall_share"] < 100
    assert m["host_loop_share"] + m["prefill_stall_share"] < 100
    # a cell of four slots and outputs of 4-24 tokens: horizons of a few
    # steps, never past the cap
    assert 1 <= m["decode_steps_per_dispatch"] <= 32
