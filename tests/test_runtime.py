"""Runtime tests: page allocator invariants, scheduler policy, and the
continuous-batching engine end-to-end (CPU reduced configs)."""

import copy

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import get_model, transformer as T
from repro.runtime import (Engine, EngineConfig, MultiQueueScheduler,
                           NEUTRAL_OWNER, PageAllocator, PagerConfig,
                           PrefixIndex, Request, Scheduler, poisson_trace,
                           run_static, shared_prefix_trace)

# --- kv_pager ------------------------------------------------------------------------


def test_allocator_conservation():
    a = PageAllocator(17)
    assert a.free_count == 16 and a.live_count == 0
    p1 = a.alloc(1, 5)
    p2 = a.alloc(2, 7)
    assert len(p1) == 5 and len(p2) == 7
    assert not set(p1) & set(p2), "pages double-allocated"
    assert 0 not in p1 + p2, "trash page handed out"
    assert a.live_count == 12 and a.free_count == 4
    a.check()
    assert a.alloc(3, 5) is None            # insufficient: no change
    assert a.free_count == 4
    a.check()
    assert a.free_owner(1) == 5
    with pytest.raises(ValueError):         # double-free raises
        a.free_owner(1)
    assert a.free_count == 9
    p3 = a.alloc(3, 9)
    assert len(p3) == 9 and not set(p3) & set(p2)
    a.check()
    a.free_owner(2)
    a.free_owner(3)
    assert a.free_count == 16 and a.live_count == 0
    a.check()


def test_allocator_check_catches_corruption():
    a = PageAllocator(9)
    a.alloc(1, 3)
    a._owned[2] = [a._owned[1][0]]          # fake a double ownership
    with pytest.raises(AssertionError):
        a.check()


def test_pager_config_geometry():
    p = PagerConfig(num_pages=9, page_size=16, max_pages_per_seq=4)
    assert p.max_context == 64
    assert p.pages_for(1) == 1 and p.pages_for(16) == 1
    assert p.pages_for(17) == 2 and p.pages_for(64) == 4
    cfg = get_config("codeqwen1.5-7b").reduced()
    assert p.page_bytes(cfg) == (2 * cfg.num_layers * 16
                                 * cfg.num_kv_heads * cfg.head_dim * 2)


# --- scheduler -----------------------------------------------------------------------


def _req(rid, arrival, admitted=-1):
    r = Request(rid=rid, prompt=np.zeros(4, np.int32), max_new_tokens=4,
                arrival=arrival)
    r.admitted_step = admitted
    return r


def test_scheduler_arrival_release_and_requeue():
    reqs = [_req(0, 5), _req(1, 0), _req(2, 3)]
    s = Scheduler(reqs)
    s.release_arrivals(0)
    assert s.peek_ready().rid == 1
    assert s.next_arrival() == 3
    s.release_arrivals(4)
    assert [s.pop_ready().rid for _ in range(2)] == [1, 2]
    s.release_arrivals(5)
    preempted = s.pop_ready()
    assert preempted.rid == 0
    s.requeue(preempted)                    # preempted keeps queue priority
    assert s.peek_ready().rid == 0
    assert s.preemptions == 1


def test_scheduler_picks_latest_admitted_victim():
    active = [(0, _req(0, 0, admitted=2)), (1, _req(1, 0, admitted=9)),
              (2, _req(2, 0, admitted=5))]
    slot, req = Scheduler.pick_victim(active)
    assert (slot, req.rid) == (1, 1)
    slot, req = Scheduler.pick_victim(active, exclude=1)
    assert (slot, req.rid) == (2, 2)
    slot, req = Scheduler.pick_victim([active[0]], exclude=0)
    assert slot == 0                        # falls back to the requester


# --- engine --------------------------------------------------------------------------


def _dense_setup():
    cfg = get_config("codeqwen1.5-7b").reduced()
    params = get_model(cfg).init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


ECFG = EngineConfig(num_slots=4, page_size=8, num_pages=33,
                    max_pages_per_seq=8, prefill_bucket=8)


def test_engine_completes_all_requests_and_recycles_slots():
    cfg, params = _dense_setup()
    trace = poisson_trace(10, mean_interarrival=0.5, prompt_lens=(6, 10),
                          gen_lens=(3, 6, 12), vocab_size=cfg.vocab_size,
                          seed=0)
    rep = Engine(cfg, params, ECFG).run(copy.deepcopy(trace))
    assert len(rep.completed) == 10
    by_rid = {r.rid: r for r in rep.completed}
    for want in trace:
        got = by_rid[want.rid]
        assert not got.truncated
        assert len(got.generated) == want.max_new_tokens
        assert got.done_step >= got.arrival
    # 10 requests through 4 slots: recycling had to happen
    assert rep.decode_steps > 0
    assert rep.prefill_calls >= 10
    # run() asserts page conservation internally (allocator.check +
    # zero live pages); reaching here means the pager balanced.


def test_engine_preempts_under_page_pressure_and_recovers():
    cfg, params = _dense_setup()
    trace = poisson_trace(8, mean_interarrival=0.2, prompt_lens=(8, 16),
                          gen_lens=(24, 40), vocab_size=cfg.vocab_size,
                          seed=1)
    tiny = EngineConfig(num_slots=4, page_size=8, num_pages=17,
                        max_pages_per_seq=8, prefill_bucket=8)
    rep = Engine(cfg, params, tiny).run(copy.deepcopy(trace))
    assert rep.preemptions > 0
    assert len(rep.completed) == 8
    assert all(len(r.generated) == r.max_new_tokens for r in rep.completed)


def test_engine_rejects_oversized_request():
    cfg, params = _dense_setup()
    # max context = 8 pages * 8 = 64; this request can never fit
    trace = [Request(rid=0, prompt=np.zeros(40, np.int32),
                     max_new_tokens=40)]
    rep = Engine(cfg, params, ECFG).run(trace)
    assert rep.completed[0].truncated


def test_engine_no_cross_request_leakage():
    """A request's greedy continuation must be identical whether it runs
    alone or interleaved with other requests in the slot batch."""
    cfg, params = _dense_setup()
    prompt = np.asarray(
        jax.random.randint(jax.random.PRNGKey(7), (12,), 0,
                           cfg.vocab_size), np.int32)
    alone = [Request(rid=0, prompt=prompt.copy(), max_new_tokens=8)]
    rep_alone = Engine(cfg, params, ECFG).run(alone)

    other = np.asarray(
        jax.random.randint(jax.random.PRNGKey(8), (9,), 0,
                           cfg.vocab_size), np.int32)
    both = [Request(rid=0, prompt=prompt.copy(), max_new_tokens=8),
            Request(rid=1, prompt=other, max_new_tokens=11)]
    rep_both = Engine(cfg, params, ECFG).run(both)

    tok_alone = rep_alone.completed[0].generated
    tok_both = {r.rid: r.generated for r in rep_both.completed}[0]
    assert tok_alone == tok_both


def test_paged_decode_matches_dense_decode():
    """Engine-grade path check: paged_decode_step reproduces the dense
    decode_step trajectory (same greedy tokens, close logits)."""
    cfg, params = _dense_setup()
    plen, gen, page = 6, 5, 4
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0,
                              cfg.vocab_size)
    import jax.numpy as jnp

    logits_d, st = T.prefill(cfg, params, {"tokens": toks[:, :plen]},
                             cache_len=plen + gen)
    ps = T.init_paged_decode_state(cfg, num_pages=8, page_size=page)
    lengths = jnp.array([plen], jnp.int32)
    last, (k, v) = T.paged_prefill(cfg, params, {"tokens": toks}, lengths)
    ps = T.write_prefill_pages(cfg, ps, (k[:, 0], v[:, 0]),
                               jnp.array([1, 2], jnp.int32))
    np.testing.assert_array_equal(np.asarray(last), np.asarray(logits_d))

    pt = np.zeros((1, 4), np.int32)
    pt[0, :3] = [1, 2, 3]
    tok_d = tok_p = jnp.argmax(logits_d, -1)
    live = plen
    for i in range(gen):
        lg_d, st = T.decode_step(cfg, params, st, tok_d)
        lg_p, ps = T.paged_decode_step(cfg, params, ps, tok_p,
                                       jnp.asarray(pt),
                                       jnp.array([live], jnp.int32),
                                       jnp.array([True]))
        tok_d = jnp.argmax(lg_d, -1)
        tok_p = jnp.argmax(lg_p, -1)
        assert int(tok_d[0]) == int(tok_p[0]), f"diverged at step {i}"
        np.testing.assert_allclose(np.asarray(lg_d), np.asarray(lg_p),
                                   rtol=0.05, atol=0.05)
        live += 1


def test_engine_deterministic_replay_with_preemptions():
    """Replaying the same Poisson trace with the same seed yields an
    identical EngineReport.summary() (wall-clock fields excluded) and
    identical per-request token streams — including through the
    preemption/requeue path, which a page-starved config forces."""
    cfg, params = _dense_setup()
    trace = poisson_trace(8, mean_interarrival=0.2, prompt_lens=(8, 16),
                          gen_lens=(24, 40), vocab_size=cfg.vocab_size,
                          seed=1)
    tiny = EngineConfig(num_slots=4, page_size=8, num_pages=17,
                        max_pages_per_seq=8, prefill_bucket=8,
                        greedy=False, temperature=0.8, seed=3)

    def go():
        rep = Engine(cfg, params, tiny).run(copy.deepcopy(trace))
        s = rep.summary()
        s.pop("wall_s")                     # timing, not behaviour
        return rep, s

    rep1, s1 = go()
    rep2, s2 = go()
    assert rep1.preemptions > 0, "trace must exercise the requeue path"
    assert s1 == s2
    toks1 = {r.rid: r.generated for r in rep1.completed}
    toks2 = {r.rid: r.generated for r in rep2.completed}
    assert toks1 == toks2
    assert [(r.rid, r.admitted_step, r.done_step, r.prefills)
            for r in rep1.completed] == \
        [(r.rid, r.admitted_step, r.done_step, r.prefills)
         for r in rep2.completed]


def test_engine_vs_static_structural_win():
    """Mixed-length trace: the engine strictly beats lockstep batching on
    tokens/step and peak KV bytes (full acceptance margin is bench_serve's
    job; the invariant here is strict dominance)."""
    cfg, params = _dense_setup()
    trace = poisson_trace(12, mean_interarrival=0.3, prompt_lens=(6, 10),
                          gen_lens=(3, 6, 24), vocab_size=cfg.vocab_size,
                          seed=5)
    eng = Engine(cfg, params, ECFG).run(copy.deepcopy(trace))
    sta = run_static(cfg, params, copy.deepcopy(trace), num_slots=4)
    assert eng.new_tokens == sta.new_tokens
    assert eng.tokens_per_step > sta.tokens_per_step
    assert eng.decode_tokens_per_step > sta.decode_tokens_per_step
    assert eng.kv_bytes_peak < sta.kv_bytes_peak
    assert eng.wasted_slot_fraction < sta.wasted_slot_fraction


def test_tokens_per_step_prices_prefill_compute():
    """The corrected structural metric folds prefill compute into the
    denominator at decode-equivalent throughput, so the decode-only
    metric strictly upper-bounds it whenever any prefill ran."""
    cfg, params = _dense_setup()
    trace = poisson_trace(8, mean_interarrival=0.4, prompt_lens=(6, 10),
                          gen_lens=(3, 6), vocab_size=cfg.vocab_size,
                          seed=4)
    rep = Engine(cfg, params, ECFG).run(copy.deepcopy(trace))
    # paged prefill computes bucket-padded tokens, once per admission
    min_bucketed = sum(-(-len(r.prompt) // ECFG.prefill_bucket)
                       * ECFG.prefill_bucket for r in trace)
    assert rep.prefill_tokens >= min_bucketed
    assert rep.prefill_equiv_steps == pytest.approx(
        rep.prefill_tokens / ECFG.num_slots)
    assert rep.tokens_per_step == pytest.approx(
        rep.new_tokens / (rep.decode_steps + rep.prefill_equiv_steps))
    assert rep.tokens_per_step < rep.decode_tokens_per_step


def test_preemption_reprefill_is_priced():
    """Re-prefill after preemption must enlarge the prefill-token
    denominator: restarted work is paid for, not free."""
    cfg, params = _dense_setup()
    trace = poisson_trace(8, mean_interarrival=0.2, prompt_lens=(8, 16),
                          gen_lens=(24, 40), vocab_size=cfg.vocab_size,
                          seed=1)
    tiny = EngineConfig(num_slots=4, page_size=8, num_pages=17,
                        max_pages_per_seq=8, prefill_bucket=8)
    rep = Engine(cfg, params, tiny).run(copy.deepcopy(trace))
    assert rep.preemptions > 0
    first_pass = sum(-(-len(r.prompt) // tiny.prefill_bucket)
                     * tiny.prefill_bucket for r in trace)
    assert rep.prefill_calls > len(trace)
    assert rep.prefill_tokens > first_pass


def test_engine_recurrent_backend():
    cfg = get_config("rwkv6-7b").reduced()
    params = get_model(cfg).init_params(cfg, jax.random.PRNGKey(0))
    trace = poisson_trace(6, mean_interarrival=0.5, prompt_lens=(6, 10),
                          gen_lens=(3, 8), vocab_size=cfg.vocab_size,
                          seed=2)
    rep = Engine(cfg, params, EngineConfig(num_slots=2)).run(
        copy.deepcopy(trace))
    assert len(rep.completed) == 6
    assert all(len(r.generated) == r.max_new_tokens for r in rep.completed)
    assert rep.page_bytes == 0              # constant-state backend


def test_engine_rejects_unsupported_family():
    cfg = get_config("whisper-tiny").reduced()
    params = get_model(cfg).init_params(cfg, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="no engine backend"):
        Engine(cfg, params, ECFG)


# --- prefix sharing ------------------------------------------------------------------


def test_allocator_share_guards_and_reclaimable_accounting():
    a = PageAllocator(9, limit=8)
    pages = a.alloc(1, 3)
    a.share(2, pages[:2])
    assert a.refcount(pages[0]) == 2
    assert a.shared_count == 2
    with pytest.raises(ValueError):
        a.share(2, pages[:1])               # already held by 2
    with pytest.raises(ValueError):
        a.share(3, [pages[0], pages[0]])    # duplicate in one call
    with pytest.raises(ValueError):
        a.share(3, [0])                     # not a live page
    a.free_owner(1)         # drops refs; the shared rows stay live
    assert a.live_count == 2
    with pytest.raises(ValueError):
        a.free_page(1, pages[0])            # 1 no longer holds it
    a.share(NEUTRAL_OWNER, pages[:2])
    assert a.neutral_count == 0             # still demanded by owner 2
    assert a.demand_count == 2
    a.free_owner(2)
    assert a.neutral_count == 2             # index-only: reclaimable
    assert a.demand_count == 0
    a.free_owner(NEUTRAL_OWNER)
    assert a.live_count == 0
    a.check()


def test_allocator_cow_copies_exactly_one_page():
    """The divergence-write dance: alloc one private page and drop the
    shared ref — live pages grow by one, no other holder's row moves."""
    a = PageAllocator(17, limit=16)
    row = a.alloc(1, 4)
    a.share(NEUTRAL_OWNER, row)             # index pins the row
    a.share(2, row)                         # a twin maps it too
    live0 = a.live_count
    target = row[2]
    new = a.alloc(1, 1)[0]                  # CoW by owner 1
    a.free_page(1, target)
    assert a.live_count == live0 + 1
    assert a.refcount(target) == 2 and a.refcount(new) == 1
    assert sorted(a.owned(2)) == sorted(row)
    assert sorted(a.owned(NEUTRAL_OWNER)) == sorted(row)
    assert sorted(a.owned(1)) \
        == sorted([*(p for p in row if p != target), new])
    a.check()


def test_allocator_refcount_conservation_walk():
    """Seeded random walk over alloc/share/free_page/free_owner against
    a holder model (hypothesis-free twin of the property suite)."""
    rng = np.random.default_rng(0)
    a = PageAllocator(17, limit=12)
    owners = tuple(range(1, 6))
    model, held = {}, {o: [] for o in owners}
    for _ in range(300):
        kind = int(rng.integers(0, 4))
        o = owners[int(rng.integers(len(owners)))]
        if kind == 0:
            want = int(rng.integers(1, 4))
            if a.can_alloc(want):
                for p in a.alloc(o, want):
                    assert p not in model   # live pages never reused
                    model[p] = {o}
                    held[o].append(p)
        elif kind == 1:
            src = owners[int(rng.integers(len(owners)))]
            cand = [p for p in held[src] if o not in model[p]]
            if cand:
                p = cand[int(rng.integers(len(cand)))]
                a.share(o, [p])
                model[p].add(o)
                held[o].append(p)
        elif kind == 2 and held[o]:
            p = held[o].pop(int(rng.integers(len(held[o]))))
            a.free_page(o, p)
            model[p].discard(o)
            if not model[p]:
                del model[p]
        elif kind == 3:
            if held[o]:
                a.free_owner(o)
                for p in held[o]:
                    model[p].discard(o)
                    if not model[p]:
                        del model[p]
                held[o] = []
            else:                           # double-free raises
                with pytest.raises(ValueError):
                    a.free_owner(o)
        a.check()
        assert a.live_count == len(model)
        assert a.shared_count == sum(len(h) >= 2 for h in model.values())
        for p, holders in model.items():
            assert a.refcount(p) == len(holders)


def test_prefix_index_match_insert_evict_lru():
    a = PageAllocator(17, limit=12)
    idx = PrefixIndex(4)
    toks = [1, 1, 1, 1, 2, 2, 2, 2, 3, 3, 3, 3]
    row = a.alloc(7, 3)
    assert idx.insert(a, toks, row) == 3
    assert all(a.refcount(p) == 2 for p in row)
    pages, covered = idx.match([1, 1, 1, 1, 2, 2, 2, 2, 9])
    assert pages == row[:2] and covered == 8
    # a partial last page that PREFIXES an indexed key tail-matches
    pages, covered = idx.match([1, 1, 1, 1, 2, 2, 2, 2, 3, 3],
                               allow_tail=True)
    assert pages == row and covered == 10
    # dedup: a twin row over the same tokens adds nothing
    row_b = a.alloc(8, 3)
    assert idx.insert(a, toks, row_b) == 0
    a.free_owner(8)
    # the populating request finishes; pages stay warm as cache
    a.free_owner(7)
    assert a.neutral_count == 3 and a.demand_count == 0
    # eviction is LRU over refcount-1 leaves; dropping a leaf exposes
    # its parent as the next candidate
    assert idx.evict_lru(a, 2) == 2
    pages, covered = idx.match(toks)
    assert covered == 4                     # only the root chunk left
    assert idx.release_all(a) == 1
    assert a.live_count == 0
    a.check()


def test_multi_queue_scheduler_oldest_ready_arrival():
    mk = lambda rid, arr, m: Request(
        rid=rid, prompt=np.zeros(4, np.int32), max_new_tokens=4,
        arrival=arr, model_id=m)
    s = MultiQueueScheduler([mk(0, 2, "a"), mk(1, 5, "b"), mk(2, 9, "a")])
    assert s.oldest_ready_arrival() is None
    s.release_arrivals(6)
    assert s.oldest_ready_arrival() == 2    # head of a's queue
    head = s.peek_ready(["a"])
    assert s.pop_ready(head).rid == 0
    assert s.oldest_ready_arrival() == 5    # b's head is now oldest
    s.release_arrivals(9)
    assert s.oldest_ready_arrival() == 5


def test_engine_prefix_sharing_equal_tokens_and_less_prefill():
    """Loose page budget, matched concurrency: sharing must reproduce
    the unshared run token-for-token while both prefill compute and
    peak KV demand drop."""
    cfg, params = _dense_setup()
    trace = shared_prefix_trace(12, overlap=0.5, prompt_len=32,
                                mean_interarrival=0.25, gen_lens=(8, 16),
                                vocab_size=cfg.vocab_size, seed=5)
    mk = lambda sharing: EngineConfig(
        num_slots=8, page_size=8, num_pages=80, max_pages_per_seq=16,
        prefill_bucket=8, prefix_sharing=sharing)
    base = Engine(cfg, params, mk(False)).run(copy.deepcopy(trace))
    shared = Engine(cfg, params, mk(True)).run(copy.deepcopy(trace))
    assert {r.rid: tuple(r.generated) for r in base.completed} \
        == {r.rid: tuple(r.generated) for r in shared.completed}
    assert shared.shared_page_hits > 0
    assert shared.prefill_tokens < base.prefill_tokens
    assert shared.prefill_tokens_saved > 0
    assert shared.kv_demand_bytes_peak < base.kv_demand_bytes_peak
    # run() asserts the index released every neutral ref and the
    # allocator drained; reaching here means no page leaked.


def test_engine_prefix_sharing_cow_under_churn_is_greedy_consistent():
    """Tight budget + verbatim re-sends: preempt/re-admit twins land a
    divergence write in a still-shared tail page, so CoW must fire. At
    bf16 the argmax gap between differently-bucketed compute paths is
    often a single quantum, so strict equality against the unshared run
    is ill-posed; instead teacher-force every generated sequence
    through a clean full-context forward and require each chosen token
    to sit within a few quanta of that position's argmax — KV
    corruption would show up as O(1) deviations."""
    import jax.numpy as jnp
    cfg, params = _dense_setup()
    trace = shared_prefix_trace(24, overlap=0.5, prompt_len=32,
                                mean_interarrival=0.25, gen_lens=(24,),
                                vocab_size=cfg.vocab_size, seed=11,
                                resend_frac=0.5)
    ecfg = EngineConfig(num_slots=8, page_size=8, num_pages=21,
                        max_pages_per_seq=16, prefill_bucket=8,
                        prefix_sharing=True)
    rep = Engine(cfg, params, ecfg).run(copy.deepcopy(trace))
    assert rep.cow_copies > 0, "the CoW path went unexercised"
    assert rep.preemptions > 0 and rep.shared_page_hits > 0
    worst = 0.0
    for r in rep.completed:
        seq = jnp.asarray([list(r.prompt) + list(r.generated)],
                          dtype=jnp.int32)
        logits = np.asarray(T.forward(cfg, params, {"tokens": seq})[0],
                            np.float64)
        start = len(r.prompt)
        for i, tok in enumerate(r.generated):
            v = logits[start + i - 1]
            worst = max(worst, float(v.max() - v[tok]))
    assert worst <= 0.0625, \
        f"decode deviates {worst} from the greedy oracle"
