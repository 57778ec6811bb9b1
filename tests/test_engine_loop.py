"""The one engine loop: ``PooledEngine`` is ``Engine``'s loop with a pool
policy at a few named points, so a pool of one tenant that is resident
from the first step serves exactly what ``Engine`` serves on the same
trace — the same tokens, the same counters and the same horizon cuts."""

import copy
import dataclasses
import time

import jax
import pytest

from repro.configs import get_config
from repro.models import get_model
from repro.runtime import (Engine, EngineConfig, ModelPool, PoolConfig,
                           PoolEngineConfig, PooledEngine, poisson_trace,
                           shared_prefix_trace, tracing)

COUNTERS = ("decode_steps", "device_dispatches", "host_syncs",
            "preemptions", "cow_copies", "prefill_tokens")


def _dense_trace(vocab):
    # verbatim re-sends on a tight page budget: preemption, prefix hits
    # and copy-on-write of a re-admitted shared tail page all fire
    return shared_prefix_trace(24, overlap=0.5, prompt_len=32,
                               mean_interarrival=0.25, gen_lens=(24,),
                               vocab_size=vocab, seed=11, resend_frac=0.5)


def _poisson(vocab):
    return poisson_trace(10, mean_interarrival=0.7, prompt_lens=(6, 10, 20),
                         gen_lens=(3, 9, 20), vocab_size=vocab, seed=2)


# family -> (arch, engine config, trace)
CASES = {
    "dense": ("codeqwen1.5-7b",
              dict(num_slots=8, page_size=8, num_pages=21,
                   max_pages_per_seq=16, prefill_bucket=8,
                   prefix_sharing=True),
              _dense_trace),
    "recurrent": ("rwkv6-7b", dict(num_slots=4), _poisson),
    "latent": ("deepseek-v2-lite-16b",
               dict(num_slots=4, page_size=8, num_pages=20,
                    max_pages_per_seq=8, prefill_bucket=8),
               _poisson),
}


def _serve(engine, requests):
    t0 = time.monotonic()
    rep = engine.run(requests)
    got = tracing.DISPATCHES.window(t0, time.monotonic())
    assert got is not None
    return rep, [(s.h, s.live, s.cause) for s in got]


@pytest.mark.parametrize("family", list(CASES))
def test_one_tenant_pool_serves_what_engine_serves(family):
    arch, kw, make_trace = CASES[family]
    cfg = get_config(arch).reduced()
    params = get_model(cfg).init_params(cfg, jax.random.PRNGKey(0))
    trace = make_trace(cfg.vocab_size)

    eng, eng_disp = _serve(Engine(cfg, params, EngineConfig(**kw)),
                           copy.deepcopy(trace))

    pool = ModelPool(PoolConfig(hbm_budget_bytes=1 << 30))
    pool.register(arch, cfg)
    assert pool.pack().entry(arch).residency == "resident"
    pcfg = PoolEngineConfig(policy="reload_aware", stream="model",
                            repartition="off", **kw)
    pooled, pool_disp = _serve(
        PooledEngine(pool, {arch: params}, pcfg),
        [dataclasses.replace(r, model_id=arch)
         for r in copy.deepcopy(trace)])

    assert pooled.stall_steps == 0
    served = lambda rep: sorted(  # noqa: E731
        (r.rid, r.generated, r.admitted_step, r.done_step)
        for r in rep.completed)
    assert served(pooled) == served(eng)
    assert {c: getattr(pooled, c) for c in COUNTERS} == \
        {c: getattr(eng, c) for c in COUNTERS}
    assert pool_disp == eng_disp
    if family == "dense":
        assert eng.preemptions > 0 and eng.cow_copies > 0
        assert eng.shared_page_hits > 0
