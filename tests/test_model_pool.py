"""Multi-tenant model pool: residency packing, eviction order, hysteresis,
and the pooled engine end-to-end (CPU reduced configs)."""

import copy

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import get_model
from repro.planner.residency import (double_buffer_bytes, layer_schedule,
                                     quant_bytes, weight_inventory)
from repro.runtime import (ModelPool, MultiQueueScheduler, PoolConfig,
                           PoolEngineConfig, PoolError, PooledEngine,
                           Request, multi_tenant_trace, partition_pages,
                           poisson_trace, shifting_mix_trace,
                           vlm_extras_fn)

KiB = 1 << 10

ZOO = ("codeqwen1.5-7b", "qwen2-vl-7b", "rwkv6-7b")


def _cfgs():
    return {a: get_config(a).reduced() for a in ZOO}


def _weight_bytes(cfg) -> int:
    return 2 * sum(t.params for t in weight_inventory(cfg))


def _pool(pcfg, demands=None):
    pool = ModelPool(pcfg)
    for a, cfg in _cfgs().items():
        pool.register(a, cfg, demand=(demands or {}).get(a, 1.0))
    pool.pack()
    return pool


# --- residency packing -----------------------------------------------------------


def test_pack_all_resident_when_budget_is_ample():
    pool = _pool(PoolConfig(hbm_budget_bytes=2 << 20, slab_frac=0.25))
    for e in pool.plan.entries:
        assert e.residency == "resident"
        assert e.reload_bytes == 0
        assert e.fits_slab
    assert pool.plan.pinned_bytes == sum(
        _weight_bytes(c) for c in _cfgs().values())


def test_pack_demand_weighting_orders_residency():
    """The demand-2 dense model pins fully before the demand-1 tenants;
    pinned bytes never exceed the pin budget."""
    pcfg = PoolConfig(hbm_budget_bytes=960 * KiB, slab_frac=0.5)
    pool = _pool(pcfg, demands={"codeqwen1.5-7b": 2.0})
    plan = pool.plan
    assert plan.entry("codeqwen1.5-7b").residency == "resident"
    assert plan.entry("qwen2-vl-7b").residency == "streamed"
    assert plan.entry("rwkv6-7b").residency == "streamed"
    assert plan.pinned_bytes <= pcfg.pin_budget_bytes
    # every model either fully pinned or its remainder fits the slab
    for e in plan.entries:
        assert 0 <= e.pinned_bytes <= e.weight_bytes
        assert e.fits_slab


def test_pack_everything_evicted_under_tiny_pin_budget():
    pcfg = PoolConfig(hbm_budget_bytes=400 * KiB, slab_frac=0.999)
    pool = _pool(pcfg)
    for e in pool.plan.entries:
        assert e.residency == "evicted"
        assert e.reload_bytes == e.weight_bytes
        assert e.fits_slab          # slab ~400 KiB > largest model


def test_pack_flags_unservable_models():
    """A model whose working set exceeds the slab is marked and refused."""
    pcfg = PoolConfig(hbm_budget_bytes=300 * KiB, slab_frac=0.3)
    pool = _pool(pcfg)
    e = pool.plan.entry("rwkv6-7b")   # 352 KiB model, 90 KiB slab
    assert not e.fits_slab
    with pytest.raises(PoolError, match="exceeds the swap slab"):
        pool.try_activate("rwkv6-7b", step=0)


def test_pack_is_deterministic():
    mk = lambda: _pool(PoolConfig(hbm_budget_bytes=960 * KiB, slab_frac=0.5),
                       demands={"codeqwen1.5-7b": 2.0})
    assert mk().plan.summary() == mk().plan.summary()


# --- layer schedule --------------------------------------------------------------


def test_layer_schedule_conserves_bytes_and_shape():
    """The forward-order slice schedule partitions the serving weight
    copy exactly: embed slice + per-layer slices (MoE: each layer's core
    slice followed by one slice PER ROUTED EXPERT, so cold experts stream
    as their own units) + head slice, byte-conserving for every family
    (including the remainder spread)."""
    for arch in ("codeqwen1.5-7b", "qwen2-vl-7b", "rwkv6-7b",
                 "olmoe-1b-7b", "deepseek-v2-lite-16b",
                 "recurrentgemma-9b", "whisper-tiny"):
        cfg = get_config(arch)
        sched = layer_schedule(cfg)
        experts = cfg.moe.num_experts if cfg.moe else 0
        assert len(sched) == 2 + cfg.num_layers * (1 + experts), arch
        assert sched[0].name == "embed" and sched[-1].name == "head"
        total = 2 * sum(t.params for t in weight_inventory(cfg))
        assert sum(s.nbytes for s in sched) == total, arch
        assert all(s.nbytes >= 0 for s in sched)
        # slices of a kind are even up to the remainder spread
        layer_b = [s.nbytes for s in sched[1:-1] if "/" not in s.name]
        assert max(layer_b) - min(layer_b) <= 1, arch
        if experts:
            exp_b = [s.nbytes for s in sched if "/exp" in s.name]
            assert len(exp_b) == cfg.num_layers * experts, arch
            assert max(exp_b) - min(exp_b) <= 1, arch
            assert min(exp_b) > 0, arch


def test_layer_schedule_include_subset_aligns():
    """A tensor-name subset keeps the slice structure aligned so pinned
    bytes can be subtracted slice-by-slice from the full schedule."""
    cfg = get_config("codeqwen1.5-7b").reduced()
    full = layer_schedule(cfg)
    sub = layer_schedule(cfg, include={"embed", "attn"})
    assert [s.name for s in sub] == [s.name for s in full]
    assert all(a.nbytes <= b.nbytes for a, b in zip(sub, full))
    inv = {t.name: t.params for t in weight_inventory(cfg)}
    assert sum(s.nbytes for s in sub) == 2 * (inv["embed"] + inv["attn"])
    assert sub[0].nbytes == 2 * inv["embed"]    # embed leads the forward
    assert sub[-1].nbytes == 0                  # lm_head not included


def test_pack_builds_aligned_reload_schedules():
    """Every packed entry carries a per-slice schedule whose pinned part
    and streamed remainder both conserve the tensor-level accounting."""
    pool = _pool(PoolConfig(hbm_budget_bytes=960 * KiB, slab_frac=0.5),
                 demands={"codeqwen1.5-7b": 2.0})
    for e in pool.plan.entries:
        assert sum(e.layer_bytes) == e.weight_bytes
        assert sum(e.pinned_layer_bytes) == e.pinned_bytes
        assert sum(e.reload_schedule) == e.reload_bytes
        assert all(0 <= p <= f for p, f in zip(e.pinned_layer_bytes,
                                               e.layer_bytes))
        # the hideable window never covers the slice-0 lead
        bw = pool.pcfg.reload_bytes_per_step
        assert e.hideable_bytes(bw) <= max(
            e.reload_bytes - e.reload_schedule[0], 0)


# --- compressed weight streaming (quant) ----------------------------------------


def test_quant_bytes_model():
    fp = 128 * 1024
    assert quant_bytes(fp, "fp") == fp
    assert quant_bytes(0, "int8") == 0
    # int8: half payload + one bf16 scale per 128 params (1/128 of fp)
    assert quant_bytes(fp, "int8") == fp // 2 + fp // 128
    assert quant_bytes(fp, "int4") == fp // 4 + fp // 128
    # ceil-rounded, never zero, never bigger than fp for real slices
    assert 0 < quant_bytes(3, "int4") <= 3


def test_layer_schedule_auto_precisions_follow_sensitivity():
    # MoE: boundary decode layers + embed/head stay int8; the routed
    # expert slices (lowest reuse per byte) drop to int4 even when they
    # hang off a boundary layer
    sched = layer_schedule(get_config("deepseek-v2-lite-16b").reduced(),
                           quant="auto")
    by_name = {s.name: s.precision for s in sched}
    assert by_name["embed"] == by_name["head"] == "int8"
    assert all(p == "int4" for n, p in by_name.items() if "/exp" in n)
    assert all(p == "int8" for n, p in by_name.items() if "/" not in n
               and n.startswith("layer"))
    # off keeps everything fp; uniform modes are uniform
    assert all(s.precision == "fp" for s in layer_schedule(
        get_config("rwkv6-7b").reduced()))
    assert all(s.precision == "int4" for s in layer_schedule(
        get_config("rwkv6-7b").reduced(), quant="int4"))


def test_pack_quant_shrinks_reload_but_not_fp_ledgers():
    """int8 streaming halves the reload schedule and the double-buffer
    pair while the fp packing ledgers (pinned bytes, layer bytes, HBM
    budget accounting) stay byte-identical to the off plan."""
    pcfg = PoolConfig(hbm_budget_bytes=960 * KiB, slab_frac=0.5)
    off = _pool(pcfg)
    i8 = _pool(PoolConfig(hbm_budget_bytes=960 * KiB, slab_frac=0.5,
                          quant="int8"))
    assert off.plan.pinned_bytes == i8.plan.pinned_bytes
    for eo, eq in zip(off.plan.entries, i8.plan.entries):
        assert eo.layer_bytes == eq.layer_bytes          # fp schedule
        assert eo.pinned_layer_bytes == eq.pinned_layer_bytes
        assert sum(eq.layer_bytes) == eq.weight_bytes    # conservation
        # the DMA-visible quantities shrink by the encoding ratio
        if eo.reload_bytes:
            ratio = eo.reload_bytes / eq.reload_bytes
            assert 1.9 <= ratio <= 2.0, (eq.model_id, ratio)
            dbr = double_buffer_bytes(eo.reload_schedule) \
                / double_buffer_bytes(eq.reload_schedule)
            assert 1.9 <= dbr <= 2.0, (eq.model_id, dbr)
        # per-slice: each quantized slice re-encodes its fp remainder
        assert eq.reload_schedule == tuple(
            quant_bytes(f - p, prec)
            for f, p, prec in zip(eq.layer_bytes, eq.pinned_layer_bytes,
                                  eq.precisions))


def test_pack_quant_flips_servability_at_tight_slab():
    """The PR-5 flip: a slab too small for a tenant's fp reload working
    set but big enough for its int8 encoding makes the tenant servable
    under quant — the whole point of compressed streaming."""
    mk = lambda q: _pool(PoolConfig(  # noqa: E731
        hbm_budget_bytes=500 * KiB, slab_frac=0.4, quant=q))
    off, i8 = mk("off"), mk("int8")
    off_srv = {e.model_id for e in off.plan.entries if e.fits_slab}
    i8_srv = {e.model_id for e in i8.plan.entries if e.fits_slab}
    assert off_srv < i8_srv, (off_srv, i8_srv)


# --- activation / eviction / hysteresis -----------------------------------------


def _all_evicted_pool(demands):
    """Pool where every tenant is evicted; slab holds exactly two of the
    transformer working sets (208.6 KiB each) but not all three models."""
    pcfg = PoolConfig(hbm_budget_bytes=500 * KiB, slab_frac=0.999,
                      reload_bytes_per_step=32 * KiB, hysteresis_steps=16)
    return _pool(pcfg, demands)


def test_activation_accounting_and_stalls():
    pool = _all_evicted_pool({})
    e = pool.plan.entry("codeqwen1.5-7b")
    stall, evicted = pool.try_activate("codeqwen1.5-7b", step=0)
    assert evicted == []
    assert stall == -(-e.reload_bytes // (32 * KiB))
    assert pool.reload_bytes_total == e.reload_bytes
    assert pool.reload_events == 1
    assert pool.is_hot("codeqwen1.5-7b")
    # re-activating a hot model is free
    assert pool.try_activate("codeqwen1.5-7b", step=5) == (0, [])
    assert pool.reload_events == 1


def test_eviction_order_is_least_value_per_byte_first():
    """rwkv6 (demand 3) outranks qwen2-vl (demand 1) outranks codeqwen
    (demand 0.5): making room evicts the cheapest-to-lose model first."""
    pool = _all_evicted_pool({"codeqwen1.5-7b": 0.5, "rwkv6-7b": 3.0})
    vals = {e.model_id: e.value_per_byte for e in pool.plan.entries}
    assert vals["codeqwen1.5-7b"] < vals["qwen2-vl-7b"] < vals["rwkv6-7b"]
    pool.try_activate("codeqwen1.5-7b", step=0)
    pool.try_activate("qwen2-vl-7b", step=0)
    # slab now holds 2 x 208.6 KiB; rwkv (352 KiB) needs both gone
    stall, evicted = pool.try_activate("rwkv6-7b", step=20)
    assert evicted == ["codeqwen1.5-7b", "qwen2-vl-7b"]
    assert pool.evictions == 2
    assert pool.hot_models() == ["rwkv6-7b"]
    # evicted model reloads (and pays) again on its next activation
    pool.try_activate("codeqwen1.5-7b", step=40)
    assert pool.reload_events == 4


def test_hysteresis_defers_thrashing_evictions():
    pool = _all_evicted_pool({"codeqwen1.5-7b": 0.5, "rwkv6-7b": 3.0})
    pool.try_activate("codeqwen1.5-7b", step=0)
    pool.try_activate("qwen2-vl-7b", step=10)
    # step 12: codeqwen's window (16) has not expired -> activation waits
    assert pool.try_activate("rwkv6-7b", step=12) is None
    assert pool.deferred_activations == 1
    assert sorted(pool.hot_models()) == ["codeqwen1.5-7b", "qwen2-vl-7b"]
    # step 20: codeqwen is evictable but qwen2-vl (hot since 10) is not,
    # and rwkv needs both slots -> still deferred
    assert pool.try_activate("rwkv6-7b", step=20) is None
    # step 26: both windows expired -> eviction proceeds in value order
    stall, evicted = pool.try_activate("rwkv6-7b", step=26)
    assert evicted == ["codeqwen1.5-7b", "qwen2-vl-7b"]


def test_protected_models_are_never_evicted():
    pool = _all_evicted_pool({"codeqwen1.5-7b": 0.5, "rwkv6-7b": 3.0})
    pool.try_activate("codeqwen1.5-7b", step=0)
    pool.try_activate("qwen2-vl-7b", step=0)
    got = pool.try_activate("rwkv6-7b", step=100,
                            protected=frozenset({"codeqwen1.5-7b"}))
    assert got is None                  # qwen2-vl alone frees too little
    assert pool.is_hot("codeqwen1.5-7b")


def test_register_after_pack_and_duplicates_rejected():
    pool = ModelPool(PoolConfig(hbm_budget_bytes=1 << 20))
    cfg = get_config("codeqwen1.5-7b").reduced()
    pool.register("m", cfg)
    with pytest.raises(PoolError, match="twice"):
        pool.register("m", cfg)
    pool.pack()
    with pytest.raises(PoolError, match="already packed"):
        pool.register("m2", cfg)


# --- multi-queue scheduler -------------------------------------------------------


def test_multi_queue_scheduler_fcfs_across_models():
    reqs = [Request(rid=0, prompt=np.zeros(4, np.int32), max_new_tokens=4,
                    arrival=2, model_id="a"),
            Request(rid=1, prompt=np.zeros(4, np.int32), max_new_tokens=6,
                    arrival=0, model_id="b"),
            Request(rid=2, prompt=np.zeros(4, np.int32), max_new_tokens=2,
                    arrival=1, model_id="a")]
    s = MultiQueueScheduler(reqs)
    s.release_arrivals(0)
    assert s.ready_models() == ["b"]
    assert s.peek_ready(["a"]) is None
    s.release_arrivals(2)
    assert s.ready_models() == ["a", "b"]
    assert s.pending_demand("a") == 6 and s.pending_demand("b") == 6
    # earliest arrival among the allowed set wins
    r = s.peek_ready(["a", "b"])
    assert r.rid == 1
    s.pop_ready(r)
    r = s.peek_ready(["a", "b"])
    assert r.rid == 2                   # a's queue stays FCFS
    s.pop_ready(r)
    s.requeue(r)                        # preemption: back to queue head
    assert s.peek_ready(["a"]).rid == 2
    assert s.preemptions == 1
    assert not s.exhausted


def test_multi_tenant_trace_shares_and_determinism():
    tenants = [dict(model_id="x", vocab_size=64, share=3.0),
               dict(model_id="y", vocab_size=32, share=1.0)]
    t1 = multi_tenant_trace(tenants, 200, mean_interarrival=0.5,
                            prompt_lens=(4, 8), gen_lens=(2, 4), seed=7)
    t2 = multi_tenant_trace(tenants, 200, mean_interarrival=0.5,
                            prompt_lens=(4, 8), gen_lens=(2, 4), seed=7)
    assert [(r.model_id, r.arrival, r.prompt.tolist()) for r in t1] == \
        [(r.model_id, r.arrival, r.prompt.tolist()) for r in t2]
    n_x = sum(1 for r in t1 if r.model_id == "x")
    assert 200 * 0.55 < n_x < 200 * 0.95       # ~75% expected
    assert all(r.prompt.max() < 64 for r in t1)
    assert all(r.prompt.max() < 32 for r in t1 if r.model_id == "y")


# --- pooled engine ---------------------------------------------------------------


POOL_ECFG = PoolEngineConfig(num_slots=4, page_size=8, num_pages=49,
                             max_pages_per_seq=8, prefill_bucket=8)


def _zoo_setup(archs=("codeqwen1.5-7b", "rwkv6-7b")):
    cfgs = {a: get_config(a).reduced() for a in archs}
    params = {a: get_model(c).init_params(c, jax.random.PRNGKey(0))
              for a, c in cfgs.items()}
    tenants = [dict(model_id=a, vocab_size=c.vocab_size,
                    extras_fn=vlm_extras_fn(c) if c.family == "vlm"
                    else None)
               for a, c in cfgs.items()]
    return cfgs, params, tenants


def test_pooled_engine_completes_all_tenants():
    cfgs, params, tenants = _zoo_setup()
    pcfg = PoolConfig(hbm_budget_bytes=700 * KiB, slab_frac=0.55,
                      reload_bytes_per_step=32 * KiB, hysteresis_steps=8)
    pool = ModelPool(pcfg)
    for a, c in cfgs.items():
        pool.register(a, c)
    trace = multi_tenant_trace(tenants, 10, mean_interarrival=0.5,
                               prompt_lens=(6, 10), gen_lens=(3, 6),
                               seed=0)
    rep = PooledEngine(pool, params, POOL_ECFG).run(copy.deepcopy(trace))
    assert len(rep.completed) == 10
    by_rid = {r.rid: r for r in rep.completed}
    for want in trace:
        got = by_rid[want.rid]
        assert not got.truncated
        assert got.model_id == want.model_id
        assert len(got.generated) == want.max_new_tokens
    assert sum(rep.model_tokens.values()) == rep.new_tokens
    assert all(v > 0 for v in rep.model_tokens.values())


def test_pooled_engine_deterministic_replay():
    cfgs, params, tenants = _zoo_setup()
    pcfg = PoolConfig(hbm_budget_bytes=700 * KiB, slab_frac=0.55,
                      reload_bytes_per_step=32 * KiB, hysteresis_steps=8)
    trace = multi_tenant_trace(tenants, 8, mean_interarrival=0.4,
                               prompt_lens=(6, 10), gen_lens=(3, 6),
                               seed=1)

    def go():
        pool = ModelPool(pcfg)
        for a, c in cfgs.items():
            pool.register(a, c)
        ecfg = PoolEngineConfig(num_slots=4, page_size=8, num_pages=49,
                                max_pages_per_seq=8, prefill_bucket=8,
                                greedy=False, temperature=0.8, seed=3)
        rep = PooledEngine(pool, params, ecfg).run(copy.deepcopy(trace))
        s = rep.summary()
        s.pop("wall_s")                     # timing, not behaviour
        return s, {r.rid: r.generated for r in rep.completed}

    assert go() == go()


def test_pooled_engine_charges_and_beats_naive_swapping():
    """The acceptance invariant at unit scale: on one interleaved trace
    the reload-aware policy is strictly ahead of round-robin swapping on
    decode tokens/step AND total weight-reload bytes."""
    cfgs, params, tenants = _zoo_setup()
    # slab (512 KiB) holds both working sets at once: reload-aware pays
    # each tenant's reload exactly once, naive swapping pays per switch
    pcfg = PoolConfig(hbm_budget_bytes=640 * KiB, slab_frac=0.8,
                      reload_bytes_per_step=8 * KiB, hysteresis_steps=16)
    trace = multi_tenant_trace(tenants, 14, mean_interarrival=0.3,
                               prompt_lens=(6, 10), gen_lens=(4, 8, 16),
                               seed=2)
    reps = {}
    for policy in ("reload_aware", "round_robin"):
        pool = ModelPool(pcfg)
        for a, c in cfgs.items():
            pool.register(a, c)
        ecfg = PoolEngineConfig(num_slots=4, page_size=8, num_pages=49,
                                max_pages_per_seq=8, prefill_bucket=8,
                                policy=policy, rr_quantum=8)
        reps[policy] = PooledEngine(pool, params, ecfg).run(
            copy.deepcopy(trace))
    ra, rr = reps["reload_aware"], reps["round_robin"]
    assert ra.new_tokens == rr.new_tokens
    assert ra.reload_bytes > 0          # reloads are really charged
    assert rr.reload_bytes > ra.reload_bytes
    assert ra.tokens_per_step > rr.tokens_per_step


def test_pooled_engine_rejects_unknown_model_id():
    """A request tagged with a model the pool never registered is failed
    fast instead of crashing the serving loop."""
    cfgs, params, _ = _zoo_setup(archs=("codeqwen1.5-7b",))
    pool = ModelPool(PoolConfig(hbm_budget_bytes=1 << 20))
    pool.register("codeqwen1.5-7b", cfgs["codeqwen1.5-7b"])
    reqs = [Request(rid=0, prompt=np.zeros(6, np.int32), max_new_tokens=3,
                    model_id="codeqwen1.5-7b"),
            Request(rid=1, prompt=np.zeros(6, np.int32), max_new_tokens=3,
                    model_id="not-a-model")]
    rep = PooledEngine(pool, params, POOL_ECFG).run(reqs)
    got = {r.rid: r.truncated for r in rep.completed}
    assert got == {0: False, 1: True}


# --- layer-granular streaming ----------------------------------------------------


def test_begin_stream_reserves_slab_and_ticks_to_ready():
    """begin_stream reserves the working set like try_activate but charges
    no up-front stall: the model is hot yet not decode-ready until the
    serial DMA has streamed all but the hideable tail."""
    pool = _all_evicted_pool({})
    bw = pool.pcfg.reload_bytes_per_step
    e = pool.plan.entry("codeqwen1.5-7b")
    assert pool.begin_stream("codeqwen1.5-7b", step=0) == []
    assert pool.is_hot("codeqwen1.5-7b")
    assert pool.slab_used == e.reload_bytes
    assert pool.reload_bytes_total == e.reload_bytes
    assert pool.stream_head == "codeqwen1.5-7b"
    assert not pool.decode_ready("codeqwen1.5-7b")
    ticks = 0
    while not pool.decode_ready("codeqwen1.5-7b"):
        assert pool.stream_tick(bw) > 0
        ticks += 1
    # never slower than the model-granular serial stall
    assert ticks <= pool.reload_stall_steps(e.reload_bytes)
    # the hideable tail is below one step of bandwidth by construction,
    # so the decode step's own tick retires the stream
    assert pool.stream_remaining("codeqwen1.5-7b") <= bw
    pool.stream_tick(bw)
    assert pool.stream_head is None
    # re-activating a hot model is free and registers no new stream
    assert pool.begin_stream("codeqwen1.5-7b", step=5) == []
    assert not pool.streaming


def test_streams_are_serial_and_streaming_models_not_evictable():
    pool = _all_evicted_pool({})
    bw = pool.pcfg.reload_bytes_per_step
    assert pool.begin_stream("codeqwen1.5-7b", step=0) == []
    assert pool.begin_stream("qwen2-vl-7b", step=0) == []
    assert pool.streaming == ("codeqwen1.5-7b", "qwen2-vl-7b")
    before = pool.stream_remaining("qwen2-vl-7b")
    pool.stream_tick(bw)
    # serial DMA: the queued stream makes no progress behind the head,
    # and can never be decode-ready while the DMA serves another model
    assert pool.stream_remaining("qwen2-vl-7b") == before
    assert pool.stream_remaining("codeqwen1.5-7b") < \
        pool.plan.entry("codeqwen1.5-7b").reload_bytes
    assert not pool.decode_ready("qwen2-vl-7b")
    # mid-stream models are never eviction victims, even past hysteresis
    assert pool.evictable(step=10_000) == []
    # evicting explicitly clears the stream state
    pool.evict("qwen2-vl-7b")
    assert pool.streaming == ("codeqwen1.5-7b",)
    assert pool.stream_remaining("qwen2-vl-7b") == 0


def test_pooled_engine_overlap_never_more_stalls_and_wins_contended():
    """Acceptance regression: on the same trace, layer-granular overlapped
    streaming never reports MORE stall steps than model-granular, and
    under multi-tenant contention it strictly reduces them and improves
    tokens/step."""
    cfgs, params, tenants = _zoo_setup(
        archs=("codeqwen1.5-7b", "qwen2-vl-7b", "rwkv6-7b"))
    pcfg = PoolConfig(hbm_budget_bytes=960 * KiB, slab_frac=0.5,
                      reload_bytes_per_step=16 * KiB, hysteresis_steps=32)
    trace = multi_tenant_trace(tenants, 16, mean_interarrival=0.3,
                               prompt_lens=(6, 10), gen_lens=(4, 8, 16),
                               seed=5)
    reps = {}
    for stream in ("model", "layer"):
        pool = ModelPool(pcfg)
        for a, c in cfgs.items():
            pool.register(a, c, demand=2.0 if c.family == "dense" else 1.0)
        ecfg = PoolEngineConfig(num_slots=6, page_size=8, num_pages=65,
                                max_pages_per_seq=8, prefill_bucket=8,
                                stream=stream)
        reps[stream] = PooledEngine(pool, params, ecfg).run(
            copy.deepcopy(trace))
    lay, mod = reps["layer"], reps["model"]
    assert lay.new_tokens == mod.new_tokens
    assert mod.stall_steps > 0, "trace must exercise cold activations"
    assert lay.stall_steps <= mod.stall_steps
    assert lay.stall_steps < mod.stall_steps
    assert lay.tokens_per_step > mod.tokens_per_step
    for m in mod.stall_steps_by_model:
        assert lay.stall_steps_by_model[m] <= mod.stall_steps_by_model[m]


# --- per-tenant page partition ---------------------------------------------------


def test_partition_pages_proportional_and_within_budget():
    got = partition_pages(97, {"a": 2.0, "b": 1.0})
    assert sum(n + 1 for n in got.values()) <= 97
    assert got["a"] > got["b"] >= 1
    # single tenant takes the whole budget minus its trash page
    assert partition_pages(33, {"solo": 1.0}) == {"solo": 32}
    # everyone gets at least one usable page
    tiny = partition_pages(7, {"a": 100.0, "b": 1.0, "c": 1.0})
    assert all(n >= 1 for n in tiny.values())
    assert sum(n + 1 for n in tiny.values()) <= 7


def test_pooled_engine_physical_pages_match_modeled_budget():
    """The PR-2 bug: every paged tenant allocated a full num_pages device
    pool. Partitioned sub-ranges must keep the total physical backing
    (incl. per-tenant trash pages) within the modeled shared budget."""
    cfgs, params, tenants = _zoo_setup(
        archs=("codeqwen1.5-7b", "qwen2-vl-7b", "rwkv6-7b"))
    pool = ModelPool(PoolConfig(hbm_budget_bytes=2 << 20, slab_frac=0.25))
    for a, c in cfgs.items():
        pool.register(a, c, demand=2.0 if c.family == "dense" else 1.0)
    ecfg = PoolEngineConfig(num_slots=4, page_size=8, num_pages=49,
                            max_pages_per_seq=8, prefill_bucket=8)
    eng = PooledEngine(pool, params, ecfg)
    phys = 0
    for m, b in eng.backends.items():
        if not b.paged:
            continue
        pool_pages = b.state.k_pages.shape[2]     # (L, KV, P, page, dh)
        assert pool_pages == eng.page_split[m] + 1
        phys += pool_pages
    assert phys <= ecfg.num_pages, \
        f"physical pages {phys} exceed modeled budget {ecfg.num_pages}"
    # demand-proportional: the demand-2 dense tenant gets the larger range
    assert eng.page_split["codeqwen1.5-7b"] > eng.page_split["qwen2-vl-7b"]
    # and the partitioned engine still serves every tenant to completion
    trace = multi_tenant_trace(tenants, 9, mean_interarrival=0.5,
                               prompt_lens=(6, 10), gen_lens=(3, 6), seed=6)
    rep = eng.run(copy.deepcopy(trace))
    assert len(rep.completed) == 9
    assert all(not r.truncated for r in rep.completed)
    assert rep.peak_live_pages <= sum(eng.page_split.values())


# --- bounded streaming slab ------------------------------------------------------


def test_bounded_slab_need_falls_back_to_double_buffer():
    """In bounded mode a model whose reload set FITS the slab reserves it
    whole (no gratuitous re-streaming); one that overflows reserves only
    the worst adjacent slice pair and becomes servable."""
    mk = lambda mode: _pool(PoolConfig(hbm_budget_bytes=520 * KiB,
                                       slab_frac=0.6, slab_mode=mode))
    full, bnd = mk("full"), mk("bounded")
    for pool in (full, bnd):
        for e in pool.plan.entries:
            if e.model_id != "rwkv6-7b":
                assert e.slab_need == e.reload_bytes   # fits -> resident
                assert e.restream_bytes == 0
    ef, eb = full.plan.entry("rwkv6-7b"), bnd.plan.entry("rwkv6-7b")
    assert not ef.fits_slab                            # 352K > 312K slab
    assert eb.fits_slab                                # pair 288K fits
    assert eb.slab_need == double_buffer_bytes(eb.reload_schedule)
    assert eb.restream_bytes == eb.reload_bytes - eb.slab_need > 0


def test_pooled_engine_bounded_slab_serves_overflow_tenant():
    """End-to-end at a slab too small for rwkv's working set: full mode
    rejects its requests; bounded mode serves every one of them from the
    2-slice double buffer, re-streaming per decode burst, WITHOUT adding
    stall steps to the incumbent tenant."""
    cfgs, params, tenants = _zoo_setup(archs=("codeqwen1.5-7b",
                                              "rwkv6-7b"))
    trace = multi_tenant_trace(tenants, 12, mean_interarrival=0.4,
                               prompt_lens=(6, 10), gen_lens=(3, 6),
                               seed=2)
    reps = {}
    for mode in ("full", "bounded"):
        pool = ModelPool(PoolConfig(hbm_budget_bytes=520 * KiB,
                                    slab_frac=0.6,
                                    reload_bytes_per_step=16 * KiB,
                                    slab_mode=mode))
        for a, c in cfgs.items():
            pool.register(a, c, demand=2.0 if c.family == "dense" else 1.0)
        ecfg = PoolEngineConfig(num_slots=4, page_size=8, num_pages=49,
                                max_pages_per_seq=8, prefill_bucket=8,
                                stream="layer")
        reps[mode] = PooledEngine(pool, params, ecfg).run(
            copy.deepcopy(trace))
    full, bnd = reps["full"], reps["bounded"]
    rejected = [r for r in full.completed if r.model_id == "rwkv6-7b"]
    assert rejected and all(r.truncated for r in rejected)
    assert all(not r.truncated for r in bnd.completed)
    assert bnd.restream_bytes > 0
    assert bnd.reload_bytes >= full.reload_bytes + bnd.restream_bytes \
        - full.restream_bytes
    # the DMA-bound re-stream cost lands on rwkv alone; the incumbent's
    # stalls are unchanged
    assert bnd.stall_steps_by_model["codeqwen1.5-7b"] \
        <= full.stall_steps_by_model["codeqwen1.5-7b"]
    assert bnd.stall_steps_by_model["rwkv6-7b"] > 0


def test_bounded_slab_paged_tenant_growth_waits_with_decode():
    """Regression: a PAGED tenant blocked mid-re-stream must not re-run
    the page-growth path on every blocked step — growth fired while
    lengths stood still, overwriting the same table row with a fresh
    page each step (orphaning the old one) until the lease drained and
    the tenant preempted itself. deepseek's latent pages + a working set
    that overflows the slab reproduce it: with growth gated on
    decode_ready the run completes with zero preemptions and a live-page
    peak that tracks real context, not the blocked-step count."""
    arch = "deepseek-v2-lite-16b"
    cfg = get_config(arch).reduced()
    params = {arch: get_model(cfg).init_params(cfg, jax.random.PRNGKey(0))}
    pool = ModelPool(PoolConfig(hbm_budget_bytes=170 * KiB, slab_frac=0.6,
                                reload_bytes_per_step=16 * KiB,
                                slab_mode="bounded"))
    pool.register(arch, cfg)
    assert pool.pack().entry(arch).restream_bytes > 0
    trace = poisson_trace(6, mean_interarrival=0.5, prompt_lens=(6, 10),
                          gen_lens=(8, 16), vocab_size=cfg.vocab_size,
                          seed=4)
    for r in trace:
        r.model_id = arch
    ecfg = PoolEngineConfig(num_slots=3, page_size=8, num_pages=17,
                            max_pages_per_seq=8, prefill_bucket=8,
                            stream="layer")
    rep = PooledEngine(pool, params, ecfg).run(copy.deepcopy(trace))
    assert all(not r.truncated for r in rep.completed)
    assert rep.restream_bytes > 0          # really ran the blocked path
    assert rep.preemptions == 0            # no lease-draining growth spin
    # 3 slots x at most pages_for(10 + 16) = 4 pages of real context
    assert rep.peak_live_pages <= 3 * 4


def test_bounded_slab_requires_layer_streaming():
    cfgs, params, _ = _zoo_setup(archs=("codeqwen1.5-7b",))
    pool = ModelPool(PoolConfig(hbm_budget_bytes=1 << 20,
                                slab_mode="bounded"))
    pool.register("codeqwen1.5-7b", cfgs["codeqwen1.5-7b"])
    with pytest.raises(AssertionError, match="layer"):
        PooledEngine(pool, params,
                     PoolEngineConfig(num_slots=2, stream="model"))


# --- load-driven repartitioning --------------------------------------------------


def test_pooled_engine_epoch_repartition_tracks_shifting_mix():
    """A shifting traffic mix (dense-heavy -> vlm-heavy) against a tight
    page budget: the static init-time partition starves the phase-2
    tenant into preemptions, epoch repartitioning moves free pages after
    the watermarks and must not lose throughput (the arena asserts
    conservation/disjointness/ceiling at every epoch inside run())."""
    cfgs, params, tenants = _zoo_setup(archs=("codeqwen1.5-7b",
                                              "qwen2-vl-7b"))
    for t in tenants:
        t["share"] = 3.0 if t["model_id"] == "codeqwen1.5-7b" else 1.0
    trace = shifting_mix_trace(tenants, 24, mean_interarrival=0.6,
                               prompt_lens=(8, 16), gen_lens=(8, 16, 24),
                               seed=5)
    reps, engines = {}, {}
    for repart in ("off", "epoch"):
        pool = ModelPool(PoolConfig(hbm_budget_bytes=2 << 20,
                                    slab_frac=0.25))
        for a, c in cfgs.items():
            pool.register(a, c, demand=3.0 if c.family == "dense" else 1.0)
        ecfg = PoolEngineConfig(num_slots=6, page_size=8, num_pages=25,
                                max_pages_per_seq=8, prefill_bucket=8,
                                repartition=repart, epoch_steps=16)
        engines[repart] = PooledEngine(pool, params, ecfg)
        reps[repart] = engines[repart].run(copy.deepcopy(trace))
    off, epoch = reps["off"], reps["epoch"]
    assert off.new_tokens == epoch.new_tokens
    assert off.repartitions == 0 and off.pages_moved == 0
    assert epoch.repartitions > 0 and epoch.pages_moved > 0
    # the phase-2-heavy tenant's lease really grew past its static share
    arena = engines["epoch"].arena
    assert arena.lease("qwen2-vl-7b") > arena.page_split["qwen2-vl-7b"]
    assert epoch.tokens_per_step >= off.tokens_per_step
    assert epoch.preemptions <= off.preemptions


def test_pooled_engine_repartition_off_is_static():
    """repartition='off' IS the PR-3 static partition: device pools sized
    exactly to the leases and no epoch ever moves a page."""
    cfgs, params, tenants = _zoo_setup(archs=("codeqwen1.5-7b",
                                              "qwen2-vl-7b"))
    pool = ModelPool(PoolConfig(hbm_budget_bytes=2 << 20, slab_frac=0.25))
    for a, c in cfgs.items():
        pool.register(a, c)
    eng = PooledEngine(pool, params, POOL_ECFG)
    for m, n in eng.page_split.items():
        assert eng.arena.cap(m) == n
    trace = multi_tenant_trace(tenants, 8, mean_interarrival=0.5,
                               prompt_lens=(6, 10), gen_lens=(3, 6),
                               seed=9)
    rep = eng.run(copy.deepcopy(trace))
    assert rep.repartitions == 0 and rep.pages_moved == 0


# --- admission aging bound -------------------------------------------------------


def _aging_zoo():
    cfgs = {a: get_config(a).reduced()
            for a in ("codeqwen1.5-7b", "qwen2-vl-7b")}
    params = {a: get_model(c).init_params(c, jax.random.PRNGKey(0))
              for a, c in cfgs.items()}
    return cfgs, params


def _aging_run(cfgs, params, max_bypass: int):
    """Tenant A's head (rid 1) is page-blocked behind its own running
    request while tenant B's later arrivals keep taking the free slots —
    the tenant-local-FCFS bypass the aging bound caps."""
    pool = ModelPool(PoolConfig(hbm_budget_bytes=2 << 20, slab_frac=0.25))
    for a, c in cfgs.items():
        pool.register(a, c, demand=1.0 if c.family == "dense" else 3.0)
    ecfg = PoolEngineConfig(num_slots=4, page_size=8, num_pages=13,
                            max_pages_per_seq=8, prefill_bucket=8,
                            max_bypass_steps=max_bypass)
    A, B = "codeqwen1.5-7b", "qwen2-vl-7b"
    reqs = [Request(rid=0, prompt=np.zeros(16, np.int32),
                    max_new_tokens=8, arrival=0, model_id=A),
            Request(rid=1, prompt=np.zeros(16, np.int32),
                    max_new_tokens=8, arrival=1, model_id=A)]
    reqs += [Request(rid=2 + i, prompt=np.zeros(8, np.int32),
                     max_new_tokens=4, arrival=1 + i, model_id=B)
             for i in range(12)]
    eng = PooledEngine(pool, params, ecfg)
    assert eng.page_split[A] == 3     # rid 0 holds the whole lease
    rep = eng.run(copy.deepcopy(reqs))
    assert all(not r.truncated for r in rep.completed)
    return rep, {r.rid: r for r in rep.completed}


def test_admission_aging_bound_blocks_indefinite_bypass():
    cfgs, params = _aging_zoo()
    free_rep, free = _aging_run(cfgs, params, max_bypass=0)
    aged_rep, aged = _aging_run(cfgs, params, max_bypass=3)
    assert free_rep.aging_blocks == 0
    assert aged_rep.aging_blocks > 0
    blocked_at, admitted = 1, aged[1].admitted_step
    window = range(blocked_at + 3, admitted)
    # unbounded: neighbours admit straight through the starved head's
    # whole wait; bounded: the scan blocks once the head ages, so no
    # later arrival is admitted past it until its pages free
    assert any(free[r].admitted_step in window for r in range(2, 14))
    assert not any(aged[r].admitted_step in window for r in range(2, 14))
    # the bound reorders admissions, it never loses work
    assert free_rep.new_tokens == aged_rep.new_tokens


def test_pooled_engine_rejects_unservable_tenant():
    """Requests for a model whose working set cannot fit the slab are
    failed fast; the other tenants are unaffected."""
    cfgs, params, tenants = _zoo_setup()
    # slab 90 KiB: rwkv (352 KiB, evicted) cannot ever activate
    pcfg = PoolConfig(hbm_budget_bytes=300 * KiB, slab_frac=0.3,
                      reload_bytes_per_step=32 * KiB)
    pool = ModelPool(pcfg)
    for a, c in cfgs.items():
        pool.register(a, c)
    pool.pack()
    assert pool.plan.entry("codeqwen1.5-7b").fits_slab
    assert not pool.plan.entry("rwkv6-7b").fits_slab
    trace = multi_tenant_trace(tenants, 8, mean_interarrival=0.5,
                               prompt_lens=(6,), gen_lens=(3, 6), seed=4)
    rep = PooledEngine(pool, params, POOL_ECFG).run(copy.deepcopy(trace))
    assert len(rep.completed) == 8
    for r in rep.completed:
        if r.model_id == "rwkv6-7b":
            assert r.truncated and not r.generated
        else:
            assert not r.truncated
            assert len(r.generated) == r.max_new_tokens
