"""Hybrid + MoE/MLA engine backends: per-family differentials against the
static-path oracle, the window-eviction edge case, and the 5-family pool
(CPU reduced configs)."""

import copy
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import get_model, griffin as G
from repro.runtime import (Engine, EngineConfig, HybridBackend,
                           LatentBackend, ModelPool, PoolConfig,
                           PoolEngineConfig, PooledEngine, Request,
                           engine_backend, multi_tenant_trace,
                           vlm_extras_fn)

KiB = 1 << 10

ECFG = EngineConfig(num_slots=2, page_size=8, num_pages=33,
                    max_pages_per_seq=8, prefill_bucket=8)


def _setup(arch):
    cfg = get_config(arch).reduced()
    params = get_model(cfg).init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _static_oracle(cfg, params, prompt, gen):
    """Greedy continuation on the lockstep path (B=1, no padding)."""
    api = get_model(cfg)
    logits, state = api.prefill(
        cfg, params, {"tokens": jnp.asarray(prompt[None].astype(np.int32))},
        len(prompt) + gen)
    toks = [int(np.argmax(np.asarray(logits[0])))]
    for _ in range(gen - 1):
        logits, state = api.decode_step(cfg, params, state,
                                        jnp.asarray([toks[-1]], jnp.int32))
        toks.append(int(np.argmax(np.asarray(logits[0]))))
    return toks


def _engine_tokens(cfg, params, prompt, gen, ecfg=ECFG):
    rep = Engine(cfg, params, ecfg).run(
        [Request(rid=0, prompt=prompt.copy(), max_new_tokens=gen)])
    (req,) = rep.completed
    assert not req.truncated
    return req.generated, rep


@pytest.mark.parametrize("arch", ["recurrentgemma-9b",
                                  "deepseek-v2-lite-16b"])
def test_paged_backend_matches_static_oracle(arch):
    """The engine's paged decode (window ring / latent pages) reproduces
    the static path's greedy trajectory token-for-token."""
    cfg, params = _setup(arch)
    prompt = np.asarray(jax.random.randint(jax.random.PRNGKey(7), (6,), 0,
                                           cfg.vocab_size), np.int32)
    want = _static_oracle(cfg, params, prompt, 8)
    got, rep = _engine_tokens(cfg, params, prompt, 8)
    assert got == want
    assert rep.page_bytes > 0            # really paged, not static


def test_hybrid_window_eviction_prompt_longer_than_window():
    """Prompt (20) far past the attention window (8): admission allocates
    only the in-window pages, decode matches the oracle across ring
    wraps, and the slot never holds more than ring_rows pages."""
    cfg, params = _setup("recurrentgemma-9b")
    win = cfg.recurrent.window
    prompt = np.asarray(jax.random.randint(jax.random.PRNGKey(9),
                                           (win + 12,), 0, cfg.vocab_size),
                        np.int32)
    want = _static_oracle(cfg, params, prompt, 10)
    # pool smaller than the prompt's naive page demand: only the window
    # ring is ever resident, so this still completes without preemption
    tiny = EngineConfig(num_slots=1, page_size=8, num_pages=4,
                        max_pages_per_seq=4, prefill_bucket=8)
    got, rep = _engine_tokens(cfg, params, prompt, 10, tiny)
    assert got == want
    R = G.ring_rows(win, tiny.page_size)
    assert rep.peak_live_pages <= R
    assert rep.preemptions == 0


def test_hybrid_paged_decode_logits_close_to_ring_decode():
    """Model-level differential: paged window decode vs the dense ring
    cache, same greedy tokens and close logits through a ring wrap."""
    cfg, params = _setup("recurrentgemma-9b")
    api = get_model(cfg)
    page, R = 4, G.ring_rows(get_config("recurrentgemma-9b")
                             .reduced().recurrent.window, 4)
    plen, gen = 8, 10
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, plen), 0,
                              cfg.vocab_size)
    logits_d, st = api.prefill(cfg, params, {"tokens": toks},
                               plen + gen)
    ps = G.init_paged_decode_state(cfg, num_slots=1, num_pages=8,
                                   page_size=page)
    last, kv, conv, h = G.paged_prefill(cfg, params, {"tokens": toks},
                                        jnp.asarray(plen, jnp.int32))
    # prompt pages: in-window page numbers plen-win .. plen-1 -> 1, 2
    n_lo = max(0, plen - cfg.recurrent.window) // page
    n_hi = (plen - 1) // page
    pages = list(range(1, 2 + n_hi - n_lo))
    pids = np.zeros((plen // page,), np.int32)
    for i, pg in enumerate(pages):
        pids[n_lo + i] = pg
    ps = G.write_prefill_state(cfg, ps, (kv[0][:, 0], kv[1][:, 0]),
                               conv, h, jnp.asarray(pids), 0)
    np.testing.assert_allclose(np.asarray(last), np.asarray(logits_d),
                               rtol=1e-4, atol=1e-4)

    pt = np.zeros((1, 4), np.int32)
    for i, pg in zip(range(n_lo, n_hi + 1), pages):
        pt[0, i % R] = pg
    free = iter(range(2 + n_hi - n_lo, 8))
    tok_d = tok_p = jnp.argmax(logits_d, -1)
    live = plen
    for i in range(gen):
        if live % page == 0:            # engine-side ring growth
            pt[0, (live // page) % R] = next(free)
        lg_d, st = api.decode_step(cfg, params, st, tok_d)
        lg_p, ps = G.paged_decode_step(cfg, params, ps, tok_p,
                                       jnp.asarray(pt),
                                       jnp.asarray([live], jnp.int32),
                                       jnp.asarray([True]))
        tok_d = jnp.argmax(lg_d, -1)
        tok_p = jnp.argmax(lg_p, -1)
        assert int(tok_d[0]) == int(tok_p[0]), f"diverged at step {i}"
        np.testing.assert_allclose(np.asarray(lg_d), np.asarray(lg_p),
                                   rtol=0.05, atol=0.05)
        live += 1


@pytest.mark.parametrize("arch", ["recurrentgemma-9b",
                                  "deepseek-v2-lite-16b"])
def test_engine_completes_interleaved_requests(arch):
    cfg, params = _setup(arch)
    from repro.runtime import poisson_trace
    trace = poisson_trace(6, mean_interarrival=0.5, prompt_lens=(6, 10),
                          gen_lens=(3, 8), vocab_size=cfg.vocab_size,
                          seed=2)
    rep = Engine(cfg, params, ECFG).run(copy.deepcopy(trace))
    assert len(rep.completed) == 6
    assert all(len(r.generated) == r.max_new_tokens for r in rep.completed)
    # interleaving must not leak across slots: each request's greedy
    # continuation equals its solo run
    solo = Engine(cfg, params, ECFG).run(
        [Request(rid=0, prompt=trace[0].prompt.copy(),
                 max_new_tokens=trace[0].max_new_tokens)])
    by_rid = {r.rid: r.generated for r in rep.completed}
    assert by_rid[trace[0].rid] == solo.completed[0].generated


def test_latent_engine_matches_oracle_at_tight_capacity():
    """Serving routes dropless whatever the capacity factor: at a factor
    so tight that training's capacity routing drops claims (its forward
    logits change), the engine's bucket-padded prefill and fused decode
    still reproduce the dropless static oracle token-for-token, and the
    capacity factor changes neither the oracle nor the engine."""
    cfg, params = _setup("deepseek-v2-lite-16b")
    tight = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=0.25))
    # 30 tokens: a held expert's share of the 60 claims overflows the
    # tight ceiling, and the prompt pads to a 32-token bucket
    prompt = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (30,), 0,
                                           cfg.vocab_size), np.int32)
    api = get_model(cfg)
    batch = {"tokens": jnp.asarray(prompt[None])}
    dropped = api.forward(tight, params, batch)
    assert not np.allclose(np.asarray(dropped),
                           np.asarray(api.forward(cfg, params, batch))), \
        "capacity routing drops nothing: the differential is vacuous"
    want = _static_oracle(cfg, params, prompt, 8)
    assert _static_oracle(tight, params, prompt, 8) == want
    got, rep = _engine_tokens(tight, params, prompt, 8)
    assert got == want
    assert rep.prefill_tokens > len(prompt), \
        "prefill was not bucket-padded: the padded-ceiling path is idle"


def test_latent_preemption_replays_routing():
    """Preempting a routed (MoE) request must not change its tokens: the
    re-prefill of prompt + generated tokens at a longer length routes
    dropless, as the first prefill and every decode step did, so the
    trajectory stays token-for-token equal to the no-preemption run."""
    cfg, params = _setup("deepseek-v2-lite-16b")
    prompt = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (6,), 0,
                                           cfg.vocab_size), np.int32)

    def reqs():
        return [Request(rid=i, prompt=prompt.copy(), max_new_tokens=12)
                for i in range(2)]

    calm = Engine(cfg, params, ECFG).run(reqs())
    assert calm.preemptions == 0
    # 3 usable pages for two requests needing 3 pages each at full
    # context: page growth must evict and later re-prefill one of them
    tight = EngineConfig(num_slots=2, page_size=8, num_pages=4,
                         max_pages_per_seq=8, prefill_bucket=8)
    squeezed = Engine(cfg, params, tight).run(reqs())
    assert squeezed.preemptions > 0
    assert max(r.prefills for r in squeezed.completed) > 1
    assert {r.rid: r.generated for r in squeezed.completed} \
        == {r.rid: r.generated for r in calm.completed}


def test_backend_registry_and_error_message():
    """moe routes through the latent backend only with an MLA cache; the
    unknown-family error derives its list from the live registry."""
    assert engine_backend(get_config("deepseek-v2-lite-16b").reduced()) \
        is LatentBackend
    assert engine_backend(get_config("recurrentgemma-9b").reduced()) \
        is HybridBackend
    assert engine_backend(get_config("olmoe-1b-7b").reduced()) is None
    cfg = get_config("olmoe-1b-7b").reduced()
    params = get_model(cfg).init_params(cfg, jax.random.PRNGKey(0))
    with pytest.raises(ValueError) as ei:
        Engine(cfg, params, ECFG)
    msg = str(ei.value)
    assert "no engine backend" in msg
    # the supported list is derived from ENGINE_FAMILIES, not hardcoded
    from repro.runtime import ENGINE_FAMILIES
    assert str(sorted(ENGINE_FAMILIES)) in msg


def test_pool_serves_five_families_end_to_end():
    """The 5-family zoo (dense/vlm/ssm/hybrid/moe) runs through ONE
    pooled engine — every tenant completes, no static fallback, and the
    hybrid tenant's pages stay window-bounded."""
    archs = ("codeqwen1.5-7b", "qwen2-vl-7b", "rwkv6-7b",
             "recurrentgemma-9b", "deepseek-v2-lite-16b")
    cfgs = {a: get_config(a).reduced() for a in archs}
    params = {a: get_model(c).init_params(c, jax.random.PRNGKey(0))
              for a, c in cfgs.items()}
    tenants = [dict(model_id=a, vocab_size=c.vocab_size,
                    extras_fn=vlm_extras_fn(c) if c.family == "vlm"
                    else None)
               for a, c in cfgs.items()]
    pool = ModelPool(PoolConfig(hbm_budget_bytes=2000 * KiB,
                                slab_frac=0.5,
                                reload_bytes_per_step=32 * KiB,
                                hysteresis_steps=8))
    for a, c in cfgs.items():
        pool.register(a, c)
    ecfg = PoolEngineConfig(num_slots=6, page_size=8, num_pages=97,
                            max_pages_per_seq=8, prefill_bucket=8)
    eng = PooledEngine(pool, params, ecfg)
    assert {cfgs[a].family for a in archs} == \
        {"dense", "vlm", "ssm", "hybrid", "moe"}
    trace = multi_tenant_trace(tenants, 15, mean_interarrival=0.4,
                               prompt_lens=(6, 10), gen_lens=(3, 6),
                               seed=3)
    rep = eng.run(copy.deepcopy(trace))
    assert len(rep.completed) == 15
    assert all(not r.truncated for r in rep.completed)
    assert all(len(r.generated) == r.max_new_tokens for r in rep.completed)
    served = {m for m, n in rep.model_tokens.items() if n > 0}
    got_families = {cfgs[a].family for a in served}
    assert {"hybrid", "moe"} <= got_families
    # physical paging: all four paged tenants split the modeled budget
    phys = sum(eng.page_split[m] + 1 for m in eng.page_split)
    assert phys <= ecfg.num_pages
    assert set(eng.page_split) == {a for a in archs
                                   if cfgs[a].family != "ssm"}
