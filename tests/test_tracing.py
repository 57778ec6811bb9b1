"""The engine loop's spans and counters (runtime/tracing.py): the counter
ring's bounds and time clipping, the cause recorded for each cut of the
fused horizon, the decode tokens the dispatch samples account for, and the
span names a profiler trace of the loop holds, run by ``Engine`` and by a
two-tenant ``PooledEngine``."""

import copy
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import get_model
from repro.runtime import (Engine, EngineConfig, ModelPool, PoolConfig,
                           PoolEngineConfig, PooledEngine, Request,
                           multi_tenant_trace, poisson_trace)
from repro.runtime import tracing

SPANS = {"engine.step", "engine.admit", "engine.prefill", "engine.grow",
         "engine.sync", "engine.decode", "engine.wait", "engine.deliver"}


@pytest.fixture(scope="module")
def dense():
    cfg = get_config("codeqwen1.5-7b").reduced()
    return cfg, get_model(cfg).init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def recurrent():
    cfg = get_config("rwkv6-7b").reduced()
    return cfg, get_model(cfg).init_params(cfg, jax.random.PRNGKey(0))


def _req(rid, plen, gen, vocab, arrival=0):
    prompt = np.random.default_rng(rid).integers(0, vocab, plen)
    return Request(rid=rid, prompt=prompt.astype(np.int32),
                   max_new_tokens=gen, arrival=arrival)


def _engine_and_trace(kind, dense, recurrent, n, kw, lens):
    """``Engine`` over the dense model with a Poisson trace of ``n``
    requests, or a pool of the dense and the recurrent model, both
    resident, with a trace that interleaves them."""
    (dcfg, dparams), (rcfg, rparams) = dense, recurrent
    if kind == "engine":
        return (Engine(dcfg, dparams, EngineConfig(**kw)),
                poisson_trace(n, vocab_size=dcfg.vocab_size, **lens))
    pool = ModelPool(PoolConfig(hbm_budget_bytes=1 << 30))
    pool.register("dense", dcfg)
    pool.register("recurrent", rcfg)
    engine = PooledEngine(pool, {"dense": dparams, "recurrent": rparams},
                          PoolEngineConfig(**kw))
    tenants = [dict(model_id="dense", vocab_size=dcfg.vocab_size),
               dict(model_id="recurrent", vocab_size=rcfg.vocab_size)]
    return engine, multi_tenant_trace(tenants, n, **lens)


def _dispatches(engine, requests):
    """The run's report and the dispatch samples it recorded."""
    t0 = time.monotonic()
    rep = engine.run(requests)
    got = tracing.DISPATCHES.window(t0, time.monotonic())
    assert got is not None
    return rep, got


# --- the ring ------------------------------------------------------------------


def test_ring_bounds_its_size_and_clips_by_time():
    ring = tracing.Ring(("v",), capacity=4)
    t0 = time.monotonic()
    for v in range(3):
        ring.add(v)
    t1 = time.monotonic()
    assert [s.v for s in ring.window(t0, t1)] == [0, 1, 2]
    assert all(t0 < s.t <= t1 for s in ring.window(t0, t1))
    mid = ring.window(t0, t1)[1].t
    assert [s.v for s in ring.window(mid, t1)] == [2]  # (t0, t1]
    assert ring.window(t1, t1 + 1.0) == []
    for v in range(3, 7):
        ring.add(v)                      # 0, 1 and 2 fall out
    t2 = time.monotonic()
    assert len(ring._buf) == 4
    assert ring.window(t0, t2) is None   # the window lost samples
    assert [s.v for s in ring.window(t1, t2)] == [3, 4, 5, 6]


# --- the cause of each cut -----------------------------------------------------


def _cut_page(dense, recurrent):
    """One slot at 6 of 8 rows: the page boundary comes first."""
    cfg, params = dense
    ecfg = EngineConfig(num_slots=1, page_size=8, num_pages=9,
                        max_pages_per_seq=8, prefill_bucket=8)
    return Engine(cfg, params, ecfg), [_req(0, 6, 20, cfg.vocab_size)]


def _cut_finish(dense, recurrent):
    """No pages; the request's budget ends before the cap."""
    cfg, params = recurrent
    ecfg = EngineConfig(num_slots=1, horizon=32)
    return Engine(cfg, params, ecfg), [_req(0, 6, 5, cfg.vocab_size)]


def _cut_cap(dense, recurrent):
    cfg, params = recurrent
    ecfg = EngineConfig(num_slots=1, horizon=4)
    return Engine(cfg, params, ecfg), [_req(0, 6, 20, cfg.vocab_size)]


def _cut_admit(dense, recurrent):
    """Two slots, pages for one request: the second waits with a slot
    free, so admission is retried every step."""
    cfg, params = dense
    ecfg = EngineConfig(num_slots=2, page_size=8, num_pages=3,
                        max_pages_per_seq=4, prefill_bucket=8)
    reqs = [_req(0, 12, 3, cfg.vocab_size), _req(1, 12, 3, cfg.vocab_size)]
    return Engine(cfg, params, ecfg), reqs


def _cut_arrival(dense, recurrent):
    cfg, params = recurrent
    ecfg = EngineConfig(num_slots=2, horizon=32)
    reqs = [_req(0, 6, 20, cfg.vocab_size),
            _req(1, 6, 20, cfg.vocab_size, arrival=5)]
    return Engine(cfg, params, ecfg), reqs


def _cut_unfused(dense, recurrent):
    cfg, params = recurrent
    ecfg = EngineConfig(num_slots=1, horizon=1)
    return Engine(cfg, params, ecfg), [_req(0, 6, 5, cfg.vocab_size)]


# cause -> (engine and requests, the first dispatch's h)
CUTS = {"page": (_cut_page, 2), "finish": (_cut_finish, 4),
        "cap": (_cut_cap, 4), "admit": (_cut_admit, 1),
        "arrival": (_cut_arrival, 5), "unfused": (_cut_unfused, 1)}


@pytest.mark.parametrize("cause", list(CUTS))
def test_first_dispatch_records_its_cut(dense, recurrent, cause):
    build, h = CUTS[cause]
    engine, reqs = build(dense, recurrent)
    _, got = _dispatches(engine, reqs)
    assert (got[0].h, got[0].cause) == (h, cause)
    assert got[0].live == 1
    assert {s.cause for s in got} <= set(tracing.CAUSES)


@pytest.mark.parametrize("horizon", [1, 16])
@pytest.mark.parametrize("kind", ["engine", "pool"])
def test_dispatches_account_for_every_decode_token(dense, recurrent, kind,
                                                   horizon):
    """Sum of h x live over the dispatches equals the tokens the requests
    received from decode: all but each request's first, from prefill. The
    pool makes one dispatch per tenant that decodes in a step."""
    engine, trace = _engine_and_trace(
        kind, dense, recurrent, 8,
        dict(num_slots=4, page_size=8, num_pages=33, max_pages_per_seq=8,
             prefill_bucket=8, horizon=horizon),
        dict(mean_interarrival=0.5, prompt_lens=(6, 10),
             gen_lens=(3, 9, 20), seed=4))
    rep, got = _dispatches(engine, copy.deepcopy(trace))
    assert rep.preemptions == 0
    decoded = sum(len(r.generated) - 1 for r in rep.completed)
    assert sum(s.h * s.live for s in got) == decoded
    steps = sum(s.h for s in got)
    if kind == "engine":
        assert steps == rep.decode_steps
    else:                               # the tenants shared some steps
        assert rep.decode_steps < steps <= 2 * rep.decode_steps
    assert len(got) == steps if horizon == 1 else len(got) < steps


def test_prefills_record_prompt_and_bucket(dense):
    cfg, params = dense
    ecfg = EngineConfig(num_slots=2, page_size=8, num_pages=33,
                        max_pages_per_seq=8, prefill_bucket=16)
    t0 = time.monotonic()
    Engine(cfg, params, ecfg).run([_req(0, 6, 3, cfg.vocab_size),
                                   _req(1, 20, 3, cfg.vocab_size)])
    got = tracing.PREFILLS.window(t0, time.monotonic())
    assert [(s.prompt, s.computed) for s in got] == [(6, 16), (20, 32)]


# --- spans ---------------------------------------------------------------------


@pytest.mark.parametrize("kind", ["engine", "pool"])
def test_profiler_trace_holds_every_span(dense, recurrent, kind, tmp_path):
    from jax.profiler import ProfileData

    engine, trace = _engine_and_trace(
        kind, dense, recurrent, 4,
        dict(num_slots=2, page_size=8, num_pages=33, max_pages_per_seq=8,
             prefill_bucket=8),
        dict(mean_interarrival=0.5, prompt_lens=(6, 10), gen_lens=(3, 12),
             seed=1))
    engine.run(copy.deepcopy(trace))      # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        engine.run(copy.deepcopy(trace))
    finally:
        jax.profiler.stop_trace()
    pd = ProfileData.from_file(str(next(Path(tmp_path).rglob(
        "*.xplane.pb"))))
    names, rids = set(), set()
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("engine."):
                    names.add(ev.name)
                if ev.name == "engine.admit":
                    rids.add(dict(ev.stats)["rid"])
    assert names == SPANS
    assert {int(r) for r in rids} == {r.rid for r in trace}
