"""The harness end to end on the CPU, at a size a test run can hold.

A tiny dense cell (two layers of width 64, four slots, prompts of 12-40
tokens) is dropped into a copy of the benchmark as data only: a config file,
a traffic file, a limits file and entries in BENCHMARK.json. The harness
must find it by name, run its window through ``runtime.Engine``, and decide
``correct`` by the reference: true for the program as it is, false when the
timed path alters the tokens it produces, and false for the float8 control
in the program's place. The chip check is skipped (``run_cell`` is called
directly); the device numbers of such a run are CPU numbers and are not
reported anywhere.
"""

import time

import numpy as np
import pytest

from chipbench import bench, check, timeline
from tinycell import DEVICE, LIMITS, TEST_PEAKS, make_root

CELLS = ["tiny.tinychat", "tinyrwkv.tinychat"]


@pytest.fixture
def root(tmp_path, monkeypatch):
    from repro.launch import compile_cache
    # the tests' processes keep JAX's default cache settings
    monkeypatch.setattr(compile_cache, "use_compile_cache", lambda: "off")
    return make_root(tmp_path)


def _run(root, cell="tiny.tinychat", seed=3, seconds=1.5, trace=False):
    return bench.run_cell(root, cell, seed, seconds, trace,
                          t_start=time.monotonic(), device=DEVICE,
                          pk=TEST_PEAKS, log=lambda *_: None)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(root, cell):
    out = _run(root, cell)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    assert out["failed"] == 0 and out["attempted"] > 4
    assert out["window"]["live_at_open"] == out["window"]["num_slots"] == 4
    m = out["metrics"]
    assert set(m) == {"output_tokens_per_s", "tpot_p95_ms", "setup_s"}
    assert all(v["value"] > 0 for v in m.values())
    gap = out["checks"]["max_logit_gap"]
    assert gap["value"] < gap["limit"]


def _decode_multi(cell):
    """The module and name of the fused decode the cell's backend calls."""
    if cell.startswith("tinyrwkv"):
        from repro.models import rwkv6
        return rwkv6, "decode_multi"
    from repro.models import transformer
    return transformer, "paged_decode_multi"


@pytest.mark.parametrize("cell", CELLS)
def test_altered_tokens_are_not_correct(root, monkeypatch, cell):
    """A token altered where the fused decode produces it (one id up)."""
    mod, name = _decode_multi(cell)
    orig = getattr(mod, name)

    def altered(cfg, *a, **kw):
        out, *rest = orig(cfg, *a, **kw)
        return ((out + 1) % cfg.vocab_size, *rest)

    monkeypatch.setattr(mod, name, altered)
    out = _run(root, cell)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_unchanged_state_is_not_correct(root, monkeypatch, cell):
    """A decode step that returns its state unchanged: the tokens it
    produces never reach the KV cache or the recurrent state."""
    mod, name = _decode_multi(cell)
    orig = getattr(mod, name)

    def stale(cfg, params, state, *a, **kw):
        out, _, *rest = orig(cfg, params, state, *a, **kw)
        return (out, state, *rest)

    monkeypatch.setattr(mod, name, stale)
    out = _run(root, cell)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_half_batch_left_out_is_not_correct(root, monkeypatch, cell):
    """A decode step that advances only the first half of its slots: the
    tokens the engine hands the other half were never decoded."""
    import jax.numpy as jnp
    mod, name = _decode_multi(cell)
    orig = getattr(mod, name)

    def half(cfg, params, state, pending, lengths, remaining, *a, **kw):
        # the mask is the argument after the page table (paged) or after
        # ``remaining`` (recurrent)
        i = 1 if cell.startswith("tiny.") else 0
        a = list(a)
        B = a[i].shape[0]
        a[i] = a[i] & (jnp.arange(B) < B // 2)
        return orig(cfg, params, state, pending, lengths, remaining, *a,
                    **kw)

    monkeypatch.setattr(mod, name, half)
    out = _run(root, cell)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(root, cell):
    """The float8 reference in the program's place reads above the limit on
    the same prompts and tokens."""
    c = bench.resolve_cell(root, cell)
    run, _ = bench.serve(c, 5, 1.5, trace=False,
                         t_start=time.monotonic(), pk=TEST_PEAKS,
                         log=lambda *_: None)
    v = check.check(run, bench.load_reference(c.config), 5, control=True,
                    log=lambda *_: None)
    assert v["correct"], v
    assert not v["control"]["correct"], v
    gap = v["control"]["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"] == LIMITS[cell]


def test_window_that_cannot_fill_its_slots_fails(root, monkeypatch):
    """A page budget too small for every slot to hold a request: the window
    never opens, and the run fails instead of measuring fewer slots."""
    import json
    path = root / "chipbench/configs/tiny.json"
    spec = json.loads(path.read_text())
    spec["serving"]["kv_budget_bytes"] = 4096 * 3
    path.write_text(json.dumps(spec))
    monkeypatch.setattr(bench, "OPEN_WITHIN_S", 1.0)
    with pytest.raises(timeline.WindowNeverOpened):
        _run(root)


def test_window_counts_only_tokens_inside(root):
    cell = bench.resolve_cell(root, "tiny.tinychat")
    run, _ = bench.serve(cell, 4, 1.0, trace=False,
                         t_start=time.monotonic(), pk=TEST_PEAKS,
                         log=lambda *_: None)
    # the window closes with the first step delivered after its second
    assert 1.0 < run.close_t - run.open_t < 1.5
    inside = list(run.deliveries())
    assert inside and all(run.open_t < t <= run.close_t for *_, t in inside)
    every = [t for r in run.requests for t in r.generated.times]
    assert max(every) == run.close_t     # the stopping delivery is dropped
    assert run.setup_s > 0


def test_closing_step_counts_whole():
    """Every request's tokens of the step that closes the window count, and
    the next delivery, to a request of that step or a first token, stops
    the run without being appended."""
    w = timeline.Window(2, 1e-3)
    a, b, c = (timeline.TimedTokens(w, 10) for _ in range(3))
    a.append(1)
    b.append(1)                          # both slots live: the window opens
    assert w.open_t is not None and w.close_t is None
    a.extend([2])                        # inside
    time.sleep(2e-3)
    a.extend([3, 4])                     # the closing step
    b.extend([3, 4])
    assert w.close_t == a.times[-1] == b.times[-1] > w.open_t + 1e-3
    for more in (lambda: a.extend([5]), lambda: c.append(1)):
        with pytest.raises(timeline.WindowClosed):
            more()
    assert (a, b, c) == ([1, 2, 3, 4], [1, 3, 4], [])


@pytest.mark.parametrize("cell", CELLS)
def test_traced_run_reads_its_trace(root, monkeypatch, cell):
    # a traced run's window stops at TRACE_SECONDS, whatever --seconds asks
    monkeypatch.setattr(bench, "TRACE_SECONDS", 1.0)
    out = _run(root, cell, seconds=30.0, trace=True)
    assert out["correct"]
    assert 1.0 < out["device"]["window_s"] < 1.5
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def test_warm_up_covers_every_prefill_bucket(root):
    from repro.runtime import Engine
    cell = bench.resolve_cell(root, "tiny.tinychat")
    cfg = bench.model_config(cell.config)
    ecfg = bench.engine_config(cfg, cell.config, cell.traffic, 0)
    import jax
    from repro.models import get_model
    params = get_model(cfg).init_params(cfg, jax.random.PRNGKey(0))
    eng = Engine(cfg, params, ecfg)
    lens = bench.warm_prompt_lengths(eng, cell.traffic)
    b = ecfg.prefill_bucket
    # every bucket a first prefill (12..40) or a re-admission after
    # preemption (up to 40 + 24 - 1 tokens) can pad to
    assert lens == list(range(b, 64 + 1, b))
    assert np.all(np.asarray(lens) % b == 0)
