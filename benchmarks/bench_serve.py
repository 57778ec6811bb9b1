"""Continuous-batching engine vs static lockstep serving (CPU reduced).

One mixed-length Poisson trace is served twice per model family — by
``runtime.Engine`` (paged KV cache, slot recycling, preemption) and by
``runtime.run_static`` (the seed path: lockstep batches, dense cache) —
and the structural serving metrics are compared:

  * tokens_per_step — generated tokens per batched decode step; on equal
    step cost this is the decode tokens/s ratio (engine target: >= 2x)
  * wasted_slot_fraction — slot-steps burnt on finished/empty slots (the
    paper's idle-rows failure mode at the serving level)
  * kv_bytes_peak — peak cache bytes holding live tokens (paged) vs the
    dense batch x max_len allocation
  * p50/p95 request latency in engine steps

The ``multi_tenant`` scenario serves FIVE model families (dense, vlm,
ssm, hybrid, MLA-MoE — every pooled cache shape) from ONE shared HBM
pool (runtime.ModelPool residency packing) on the same interleaved
trace, on the roofline-calibrated DMA clock:

  * activation policies — the reload-aware scheduler must beat naive
    round-robin swapping on tokens/step AND total weight-reload bytes,
    with the hybrid and MoE tenants served through the pooled engine
    (no static fallback);
  * streaming granularity — layer-granular overlapped streaming
    (double-buffered prefetch behind compute) must strictly reduce stall
    steps vs model-granular streaming at equal HBM budget, for >= 2
    families, and improve the family-resolved tokens/step (each
    family's tokens over shared steps plus its own attributed stalls)
    for >= 2 families;
  * device-memory arena repartitioning — on a SHIFTING traffic mix
    (tenant shares reverse mid-trace, against a deliberately tight page
    budget) epoch repartitioning must match or beat the static
    demand-proportional partition on tokens/step, with the arena
    invariants (page-byte conservation, lease disjointness, live pages
    never moved, modeled budget ceiling) asserted at every epoch; the
    per-epoch watermark/move trace is emitted as a JSON row for the
    nightly artifacts;
  * a budget x slab-fraction sweep emits the residency-vs-throughput
    frontier (Fig. 9's yellow trace at serving scale) to the bench JSON
    (``--frontier smoke`` keeps one sweep point for CI). The sweep
    carries a slab-mode axis: at the smallest budget the ``bounded``
    2-slice double buffer must host at least one tenant the ``full``
    reservation refuses, paying only with that tenant's own DMA-bound
    re-stream steps (the incumbents' stalls must not grow).

A final row checks the paged decode attention kernel (interpret mode)
against the jnp oracle.

    PYTHONPATH=src python -m benchmarks.bench_serve --scenario multi_tenant
"""

from __future__ import annotations

import copy

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.kernels import ops, ref
from repro.launch.cli import add_streaming_args
from repro.models import get_model
from repro.models.transformer import forward as dense_forward
from repro.planner.residency import double_buffer_bytes
from repro.runtime import (Engine, EngineConfig, FaultSchedule, FleetConfig,
                           FleetEngine, ModelPool, PoolConfig,
                           PoolEngineConfig, PooledEngine,
                           calibrated_reload_bytes_per_step, diurnal_trace,
                           multi_tenant_trace, poisson_trace, run_static,
                           shared_prefix_trace, shifting_mix_trace,
                           vlm_extras_fn)

# one family per cache shape: dense GQA, M-RoPE vlm backbone, constant-
# state recurrence, hybrid window ring + recurrence, MoE with an MLA
# latent-compressed cache
ARCHS = ("codeqwen1.5-7b", "qwen2-vl-7b", "rwkv6-7b",
         "recurrentgemma-9b", "deepseek-v2-lite-16b")

SLOTS = 8
N_REQUESTS = 40
MEAN_INTERARRIVAL = 0.25
PROMPT_LENS = (8, 16, 24)
GEN_LENS = (4, 8, 12, 64)          # heavy skew: lockstep drains to 64

ENGINE_CFG = EngineConfig(num_slots=SLOTS, page_size=8, num_pages=97,
                          max_pages_per_seq=16, prefill_bucket=8)


def _row(rep, family):
    s = rep.summary()
    return {
        "name": f"serve_{family}_{rep.name.split('/')[0]}",
        "tokens_per_step": s["tokens_per_step"],
        "decode_tokens_per_step": s["decode_tokens_per_step"],
        "prefill_tokens": s["prefill_tokens"],
        "wasted_slot_fraction": s["wasted_slot_fraction"],
        "kv_bytes_peak": s["kv_bytes_peak"],
        "p50_steps": s["p50"],
        "p95_steps": s["p95"],
        "new_tokens": s["new_tokens"],
        "decode_steps": s["decode_steps"],
        "preemptions": s["preemptions"],
    }


def _paged_attention_oracle_err() -> float:
    rng = np.random.default_rng(0)
    B, H, KV, dh, P, page, M = 4, 8, 2, 32, 12, 8, 4
    q = jnp.asarray(rng.standard_normal((B, H, dh)), jnp.float32)
    # two layers' pools in kernel layout (L, KV, P, page, dh), layer 1 read;
    # oracle takes that layer in model layout
    kp = jnp.asarray(rng.standard_normal((2, KV, P, page, dh)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((2, KV, P, page, dh)), jnp.float32)
    pt = np.zeros((B, M), np.int32)
    lengths = np.array([5, 8, 27, 0], np.int32)
    free = iter(range(1, P))
    for b in range(B):
        for i in range(-(-int(lengths[b]) // page)):
            pt[b, i] = next(free)
    want = ref.paged_decode_attention(
        q, jnp.transpose(kp[1], (1, 2, 0, 3)),
        jnp.transpose(vp[1], (1, 2, 0, 3)), jnp.asarray(pt),
        jnp.asarray(lengths))
    got = ops.paged_decode_attention(q, kp, vp, jnp.asarray(pt),
                                    jnp.asarray(lengths), 1, impl="interpret")
    return float(np.abs(np.asarray(got) - np.asarray(want)).max())


def run_engine_vs_static() -> list[dict]:
    rows = []
    for arch in ARCHS:
        cfg = get_config(arch).reduced()
        params = get_model(cfg).init_params(cfg, jax.random.PRNGKey(0))
        extras_fn = vlm_extras_fn(cfg) if cfg.family == "vlm" else None
        trace = poisson_trace(
            N_REQUESTS, mean_interarrival=MEAN_INTERARRIVAL,
            prompt_lens=PROMPT_LENS, gen_lens=GEN_LENS,
            vocab_size=cfg.vocab_size, seed=3, extras_fn=extras_fn)
        eng = Engine(cfg, params, ENGINE_CFG).run(copy.deepcopy(trace))
        sta = run_static(cfg, params, copy.deepcopy(trace),
                         num_slots=SLOTS)
        rows.append(_row(eng, cfg.family))
        rows.append(_row(sta, cfg.family))
        row = {
            "name": f"serve_{cfg.family}_speedup",
            "arch": cfg.name,
            "tokens_per_step_ratio": round(
                eng.tokens_per_step / sta.tokens_per_step, 3),
            "decode_tokens_per_step_ratio": round(
                eng.decode_tokens_per_step / sta.decode_tokens_per_step, 3),
            "kv_bytes_ratio": round(
                sta.kv_bytes_peak / max(eng.kv_bytes_peak, 1), 3),
            "paged": eng.page_bytes > 0,
        }
        if cfg.family == "hybrid":
            # the hybrid static baseline's ring cache is ALREADY
            # O(window), so "paged < dense" is not the claim here; the
            # claim is boundedness — the page ring never exceeds
            # ring_rows pages/slot no matter how long requests run
            from repro.models.griffin import ring_rows
            bound = (SLOTS * ring_rows(cfg.recurrent.window,
                                       ENGINE_CFG.page_size)
                     * eng.page_bytes + eng.slot_state_bytes)
            row["window_bounded"] = eng.kv_bytes_peak <= bound
        rows.append(row)
    rows.append({"name": "paged_attention_oracle",
                 "max_abs_err": _paged_attention_oracle_err()})
    return rows


# --- multi-tenant pool scenario -------------------------------------------------

# one pool over all five pooled cache shapes (zoo weights ~1298 KiB at
# smoke scale); dense carries 2x the traffic
ZOO = (("codeqwen1.5-7b", 2.0), ("qwen2-vl-7b", 1.0), ("rwkv6-7b", 1.0),
       ("recurrentgemma-9b", 1.0), ("deepseek-v2-lite-16b", 1.0))
POOL_BUDGET_KIB = 1600
POOL_SLAB_FRAC = 0.5
POOL_N_REQUESTS = 40

# budget x slab-fraction frontier (Fig. 9's yellow trace at serving
# scale); the smoke variant keeps the single middle point for CI. The
# 768 KiB point is deliberately below rwkv6's full reload working set
# (352 KiB > 0.4 * 768 KiB): only the bounded 2-slice double buffer
# (288 KiB) fits, so the slab-mode axis shows a servability flip there.
FRONTIER_BUDGETS_KIB = (768, 1408, 1600, 1920)
FRONTIER_SLABS = (0.4, 0.55)
SMOKE_BUDGETS_KIB = (768,)
SMOKE_SLABS = (0.4,)


def _pool_cfg(budget_kib: int, slab_frac: float, reload_bps: int,
              slab_mode: str = "full", quant: str = "off") -> PoolConfig:
    return PoolConfig(hbm_budget_bytes=budget_kib << 10,
                      slab_frac=slab_frac,
                      reload_bytes_per_step=reload_bps,
                      hysteresis_steps=32, slab_mode=slab_mode,
                      quant=quant)


def _pool_row(rep, plan, name: str) -> dict:
    s = rep.summary()
    models = plan.summary()["models"]
    return {
        "name": name,
        "policy": s["policy"],
        "stream": s["stream"],
        "slab_mode": plan.pcfg.slab_mode,
        "tokens_per_step": s["tokens_per_step"],
        "decode_tokens_per_step": s["decode_tokens_per_step"],
        "prefill_tokens": s["prefill_tokens"],
        "reload_bytes": s["reload_bytes"],
        "restream_bytes": s["restream_bytes"],
        "reload_events": s["reload_events"],
        "stall_steps": s["stall_steps"],
        "stall_steps_by_model": s["stall_steps_by_model"],
        "evictions": s["evictions"],
        "preemptions": s["preemptions"],
        "repartitions": s["repartitions"],
        "pages_moved": s["pages_moved"],
        "aging_blocks": s["aging_blocks"],
        "wasted_slot_fraction": s["wasted_slot_fraction"],
        "new_tokens": s["new_tokens"],
        "model_tokens": s["model_tokens"],
        "servable": sum(1 for v in models.values() if v["servable"]),
        "servable_models": sorted(m for m, v in models.items()
                                  if v["servable"]),
        "residency": {m: v["residency"] for m, v in models.items()},
    }


def _zoo():
    cfgs, params, tenants = {}, {}, []
    for arch, share in ZOO:
        cfg = get_config(arch).reduced()
        cfgs[arch] = cfg
        params[arch] = get_model(cfg).init_params(cfg, jax.random.PRNGKey(0))
        tenants.append(dict(
            model_id=arch, vocab_size=cfg.vocab_size, share=share,
            extras_fn=vlm_extras_fn(cfg) if cfg.family == "vlm" else None))
    return cfgs, params, tenants


def _run_pool(cfgs, params, trace, pcfg, policy, stream, *,
              repartition="off", num_pages=97):
    pool = ModelPool(pcfg)
    for arch, share in ZOO:
        pool.register(arch, cfgs[arch], demand=share)
    plan = pool.pack()
    ecfg = PoolEngineConfig(
        num_slots=SLOTS, page_size=8, num_pages=num_pages,
        max_pages_per_seq=16, prefill_bucket=8,
        policy=policy, rr_quantum=16, stream=stream,
        repartition=repartition)
    eng = PooledEngine(pool, params, ecfg)
    rep = eng.run(copy.deepcopy(trace))
    return rep, plan, eng


def _quant_stats(plan) -> dict:
    """Plan-level compressed-streaming quantities per non-resident
    model: the (precision-encoded) reload set, the 2-slice double-buffer
    bytes of its reload schedule — the slab-granularity metric the quant
    claims are made on — and what the slab actually reserves."""
    out = {}
    for e in plan.entries:
        if e.residency == "resident":
            continue
        out[e.model_id] = {
            "reload_bytes": e.reload_bytes,
            "double_buffer_bytes": double_buffer_bytes(e.reload_schedule),
            "slab_need": e.slab_need,
        }
    return out


def _pool_tokens(rep) -> dict:
    return {r.rid: tuple(r.generated) for r in rep.completed}


def run_multi_tenant(frontier: str = "full", quant: str = "int8",
                     reload_kib: int = 0, stream: str = "layer",
                     slab_mode: str = "full") -> list[dict]:
    # the frontier loops below reuse `stream`/`slab_mode` as loop
    # variables; keep the CLI-requested values for the quant base leg
    cli_stream, cli_slab_mode = stream, slab_mode
    cfgs, params, tenants = _zoo()
    trace = multi_tenant_trace(
        tenants, POOL_N_REQUESTS, mean_interarrival=MEAN_INTERARRIVAL,
        prompt_lens=(8, 16), gen_lens=(4, 8, 24), seed=3)
    # one clock with the kernel benches: the roofline decode-cell lower
    # bound times the off-chip DMA bandwidth, scaled to the reduced zoo
    # (overridable from the shared streaming CLI)
    reload_bps = reload_kib * 1024 or calibrated_reload_bytes_per_step(
        (a, cfgs[a]) for a, _ in ZOO)
    base_cfg = _pool_cfg(POOL_BUDGET_KIB, POOL_SLAB_FRAC, reload_bps)

    rows = [{"name": "serve_pool_reload_clock",
             "reload_bytes_per_step": reload_bps}]

    # -- activation policy comparison (PR-2 claim, model-granular) -------
    reps = {}
    for policy in ("reload_aware", "round_robin"):
        rep, plan, _ = _run_pool(cfgs, params, trace, base_cfg, policy,
                                 "model")
        reps[policy] = rep
        rows.append(_pool_row(rep, plan, f"serve_pool_{policy}"))
    ra, rr = reps["reload_aware"], reps["round_robin"]
    rows.append({
        "name": "serve_pool_speedup",
        "families": len(ZOO),
        "tokens_per_step_ratio": round(
            ra.tokens_per_step / rr.tokens_per_step, 3),
        "reload_bytes_saved": rr.reload_bytes - ra.reload_bytes,
        "same_tokens": ra.new_tokens == rr.new_tokens,
    })

    # -- streaming granularity at equal HBM budget -----------------------
    sreps = {}
    for stream in ("model", "layer"):
        rep, plan, _ = _run_pool(cfgs, params, trace, base_cfg,
                                 "reload_aware", stream)
        sreps[stream] = rep
        rows.append(_pool_row(rep, plan, f"serve_pool_stream_{stream}"))
    lay, mod = sreps["layer"], sreps["model"]
    fam = {arch: cfgs[arch].family for arch, _ in ZOO}

    def fam_tps(rep, arch):
        """Family-resolved tokens/step: a family's tokens over the steps
        it cannot avoid — the shared decode+prefill denominator plus the
        stalls ATTRIBUTED to its own activations (so one family's
        regression is visible even when the global totals improve)."""
        denom = (rep.decode_steps + rep.prefill_equiv_steps
                 + rep.stall_steps_by_model[arch])
        return rep.model_tokens[arch] / max(denom, 1e-9)

    rows.append({
        "name": "serve_pool_overlap",
        "same_tokens": lay.new_tokens == mod.new_tokens,
        "stall_steps_layer": lay.stall_steps,
        "stall_steps_model": mod.stall_steps,
        "tokens_per_step_ratio": round(
            lay.tokens_per_step / mod.tokens_per_step, 3),
        "families_with_fewer_stalls": sorted(
            fam[a] for a, _ in ZOO
            if lay.stall_steps_by_model[a] < mod.stall_steps_by_model[a]),
        "families_with_better_tokens_per_step": sorted(
            fam[a] for a, _ in ZOO if fam_tps(lay, a) > fam_tps(mod, a)),
    })

    # -- load-driven repartitioning on a SHIFTING traffic mix ------------
    # the mix reverses mid-trace (dense-heavy -> MoE-heavy), so the
    # init-time demand-proportional page partition starves the phase-2
    # heavy tenant; epoch repartitioning follows the watermarks instead.
    # A deliberately tight page budget (49 pages over 4 paged tenants)
    # makes the partition the binding constraint.
    shift_trace = shifting_mix_trace(
        tenants, POOL_N_REQUESTS, mean_interarrival=MEAN_INTERARRIVAL,
        prompt_lens=(8, 16), gen_lens=(8, 16, 24), seed=3)
    rreps = {}
    for repart in ("off", "epoch"):
        rep, plan, eng = _run_pool(cfgs, params, shift_trace, base_cfg,
                                   "reload_aware", "layer",
                                   repartition=repart, num_pages=49)
        rreps[repart] = rep
        row = _pool_row(rep, plan, f"serve_pool_repartition_{repart}")
        rows.append(row)
        if repart == "epoch":
            rows.append({"name": "serve_pool_repartition_trace",
                         "arena": eng.arena.summary(),
                         "epochs": eng.arena.history})
    rows.append({
        "name": "serve_pool_repartition",
        "tokens_per_step_ratio": round(
            rreps["epoch"].tokens_per_step / rreps["off"].tokens_per_step,
            3),
        "same_tokens": rreps["epoch"].new_tokens == rreps["off"].new_tokens,
        "repartitions": rreps["epoch"].repartitions,
        "pages_moved": rreps["epoch"].pages_moved,
        "preemptions_off": rreps["off"].preemptions,
        "preemptions_epoch": rreps["epoch"].preemptions,
    })

    # -- budget x slab frontier (stream x slab-mode axes) ----------------
    budgets = SMOKE_BUDGETS_KIB if frontier == "smoke" \
        else FRONTIER_BUDGETS_KIB
    slabs = SMOKE_SLABS if frontier == "smoke" else FRONTIER_SLABS
    for budget_kib in budgets:
        for slab in slabs:
            for stream, slab_mode in (("model", "full"), ("layer", "full"),
                                      ("layer", "bounded")):
                rep, plan, _ = _run_pool(
                    cfgs, params, trace,
                    _pool_cfg(budget_kib, slab, reload_bps, slab_mode),
                    "reload_aware", stream)
                row = _pool_row(
                    rep, plan,
                    f"serve_pool_frontier/b{budget_kib}_s{slab}"
                    f"_{stream}_{slab_mode}")
                row.update(budget_kib=budget_kib, slab_frac=slab)
                rows.append(row)

    # -- compressed weight streaming (quant axis) ------------------------
    # Streamed slices travel int8/int4 with per-channel scales
    # (kernels.dequant dequantizes in the epilogue; planner.quant_bytes
    # is the byte model), so the reload set, the double-buffer pairs,
    # and the restream traffic all shrink by the encoding ratio.
    # Two legs: the base budget pins accounting + token equality per
    # mode, and the PR-5 flip point (tightest budget x slab) shows the
    # headline — rwkv6's working set compresses INTO the slab, so
    # full-mode servability flips without the bounded restream tax.
    qmodes = ("off", "int8", "int4", "auto") if frontier == "full" \
        else ("off", quant if quant != "off" else "int8")
    bmin, smin = min(budgets), min(slabs)
    qbase = {}
    for qm in qmodes:
        # the base leg honours the shared streaming CLI (--stream /
        # --slab-mode); CI and the nightly run the layer/full defaults,
        # which is what check() pins ratios against
        rep, plan, _ = _run_pool(
            cfgs, params, trace,
            _pool_cfg(POOL_BUDGET_KIB, POOL_SLAB_FRAC, reload_bps,
                      cli_slab_mode, quant=qm),
            "reload_aware", cli_stream)
        qbase[qm] = (rep, plan)
        row = _pool_row(rep, plan, f"serve_pool_quant/{qm}")
        row.update(quant=qm, quant_stats=_quant_stats(plan))
        rows.append(row)
    for qm in qmodes:
        for slab_mode in ("full", "bounded"):
            rep, plan, _ = _run_pool(
                cfgs, params, trace,
                _pool_cfg(bmin, smin, reload_bps, slab_mode, quant=qm),
                "reload_aware", "layer")
            row = _pool_row(
                rep, plan,
                f"serve_pool_quant_frontier/b{bmin}_s{smin}"
                f"_{qm}_{slab_mode}")
            row.update(budget_kib=bmin, slab_frac=smin, quant=qm,
                       quant_stats=_quant_stats(plan))
            rows.append(row)

    def _plan_totals(plan):
        st = _quant_stats(plan)
        return (sum(v["reload_bytes"] for v in st.values()),
                sum(v["double_buffer_bytes"] for v in st.values()))

    base_rep, base_plan = qbase["off"]
    base_reload, base_db = _plan_totals(base_plan)
    modes = {}
    for qm in qmodes[1:]:
        rep, plan = qbase[qm]
        q_reload, q_db = _plan_totals(plan)
        modes[qm] = {
            "plan_reload_ratio": round(base_reload / max(q_reload, 1), 3),
            "double_buffer_ratio": round(base_db / max(q_db, 1), 3),
            "run_reload_ratio": round(
                base_rep.reload_bytes / max(rep.reload_bytes, 1), 3),
            "stall_steps": rep.stall_steps,
            "same_tokens": _pool_tokens(rep) == _pool_tokens(base_rep),
        }
    rows.append({"name": "serve_pool_quant_speedup",
                 "stream": cli_stream, "slab_mode": cli_slab_mode,
                 "stall_steps_off": base_rep.stall_steps,
                 "modes": modes})
    return rows


# --- shared-prefix scenario -----------------------------------------------------

# two halves, sharing off vs on over the same trace:
#  * capacity pairs — a single dense engine with a loose page budget, so
#    both runs hold the same 8-slot concurrency and the comparison is
#    clean: prefill compute and peak KV demand both drop while decode
#    output stays token-for-token identical to the unshared oracle.
#  * churn pair — dense + MLA-MoE tenants on one pool under a page
#    budget tight enough to force preemption, re-admission through the
#    radix index, CoW on divergence writes, and epoch lease moves.
#    Preemption schedules differ between the two runs, so their decode
#    paths hit different jit bucket shapes; at bf16 the argmax gap is
#    often a single quantum (~2^-6) or an exact tie, making strict
#    equality ill-posed.  Correctness is asserted instead by teacher-
#    forcing every generated sequence through a clean full-context
#    forward: each chosen token must sit within SP_GREEDY_TOL of that
#    position's argmax.  KV corruption shows up as O(1) deviations;
#    shape-induced rounding stays at a quantum.
SP_DENSE = "codeqwen1.5-7b"
SP_MOE = "deepseek-v2-lite-16b"
SP_PROMPT_LEN = 32
SP_OVERLAPS = (0.25, 0.5, 0.75)
SP_N_DENSE = 24
SP_N_MOE = 6
SP_CAP_PAGES = 80          # loose: every admission fits, no preemption
SP_CHURN_PAGES = 33        # tight: forces preempt / re-admit / CoW
SP_CHURN_SEED = 11
SP_RESEND_FRAC = 0.5       # churn: half the requests re-send a prior
#                            conversation verbatim — the twin shape
#                            whose preempt/re-admit cycle lands a
#                            divergence write in a still-shared page
SP_GREEDY_TOL = 0.0625     # 4 bf16 quanta at logit scale ~2


def _run_sp_capacity_once(cfg, params, trace, *, sharing: bool):
    ecfg = EngineConfig(num_slots=SLOTS, page_size=8,
                        num_pages=SP_CAP_PAGES, max_pages_per_seq=16,
                        prefill_bucket=8, prefix_sharing=sharing)
    return Engine(cfg, params, ecfg).run(copy.deepcopy(trace))


def _run_sp_churn_once(cfgs, params, trace, reload_bps, *,
                       sharing: bool):
    pool = ModelPool(_pool_cfg(POOL_BUDGET_KIB, POOL_SLAB_FRAC,
                               reload_bps))
    pool.register(SP_DENSE, cfgs[SP_DENSE], demand=2.0)
    pool.register(SP_MOE, cfgs[SP_MOE], demand=1.0)
    pool.pack()
    ecfg = PoolEngineConfig(
        num_slots=SLOTS, page_size=8, num_pages=SP_CHURN_PAGES,
        max_pages_per_seq=16, prefill_bucket=8, policy="reload_aware",
        stream="model", repartition="epoch", epoch_steps=32,
        prefix_sharing=sharing)
    eng = PooledEngine(pool, {m: params[m] for m in (SP_DENSE, SP_MOE)},
                       ecfg)
    return eng.run(copy.deepcopy(trace))


def _sp_greedy_deviation(cfg, params, reqs) -> float:
    """Worst gap between the clean-forward argmax logit and the logit of
    the token actually chosen, teacher-forcing prompt+generated."""
    worst = 0.0
    for r in reqs:
        seq = jnp.asarray([list(r.prompt) + list(r.generated)],
                          dtype=jnp.int32)
        logits = np.asarray(
            dense_forward(cfg, params, {"tokens": seq})[0], np.float64)
        p = len(r.prompt)
        for i, tok in enumerate(r.generated):
            v = logits[p + i - 1]
            worst = max(worst, float(v.max() - v[tok]))
    return worst


def _sp_pair_row(name, base, shared, extra) -> dict:
    pf_saved = 1 - shared.prefill_tokens / max(base.prefill_tokens, 1)
    kv_saved = 1 - (shared.kv_demand_bytes_peak
                    / max(base.kv_demand_bytes_peak, 1))
    row = {
        "name": name,
        "prefill_tokens_base": base.prefill_tokens,
        "prefill_tokens_shared": shared.prefill_tokens,
        "prefill_tokens_saved": shared.prefill_tokens_saved,
        "prefill_saved_frac": round(pf_saved, 4),
        "kv_peak_base": base.kv_demand_bytes_peak,
        "kv_peak_shared": shared.kv_demand_bytes_peak,
        "kv_saved_frac": round(kv_saved, 4),
        # joint compute x capacity drop: superlinear in overlap when
        # both factors track it
        "product_saved_frac": round(
            1 - (1 - pf_saved) * (1 - kv_saved), 4),
        "shared_page_hits": shared.shared_page_hits,
        "cow_copies": shared.cow_copies,
        "preemptions_base": base.preemptions,
        "preemptions_shared": shared.preemptions,
        "new_tokens": shared.new_tokens,
    }
    row.update(extra)
    return row


def run_shared_prefix(smoke: bool = False) -> list[dict]:
    cfgs = {a: get_config(a).reduced() for a in (SP_DENSE, SP_MOE)}
    params = {a: get_model(cfgs[a]).init_params(cfgs[a],
                                                jax.random.PRNGKey(0))
              for a in (SP_DENSE, SP_MOE)}
    reload_bps = calibrated_reload_bytes_per_step(cfgs.items())
    overlaps = (0.5,) if smoke else SP_OVERLAPS
    n_dense = SP_N_DENSE // 2 if smoke else SP_N_DENSE
    rows = []
    for o in overlaps:                  # capacity pairs
        trace = shared_prefix_trace(
            n_dense, overlap=o, prompt_len=SP_PROMPT_LEN,
            mean_interarrival=MEAN_INTERARRIVAL, gen_lens=(8, 16),
            vocab_size=cfgs[SP_DENSE].vocab_size, seed=5,
            model_id=SP_DENSE)
        reps = {on: _run_sp_capacity_once(cfgs[SP_DENSE],
                                          params[SP_DENSE], trace,
                                          sharing=on)
                for on in (False, True)}
        toks = {on: {r.rid: tuple(r.generated)
                     for r in reps[on].completed} for on in reps}
        rows.append(_sp_pair_row(
            f"serve_shared_prefix/o{o}", reps[False], reps[True],
            {"overlap": o, "same_tokens": toks[True] == toks[False]}))
    # churn pair: fixed 50% overlap, tight pooled budget
    dense = shared_prefix_trace(
        SP_N_DENSE, overlap=0.5, prompt_len=SP_PROMPT_LEN,
        mean_interarrival=MEAN_INTERARRIVAL, gen_lens=(24,),
        vocab_size=cfgs[SP_DENSE].vocab_size, seed=SP_CHURN_SEED,
        model_id=SP_DENSE, resend_frac=SP_RESEND_FRAC)
    moe = poisson_trace(
        SP_N_MOE, mean_interarrival=4 * MEAN_INTERARRIVAL,
        prompt_lens=(8, 16), gen_lens=(4, 8),
        vocab_size=cfgs[SP_MOE].vocab_size, seed=7, model_id=SP_MOE)
    for r in moe:
        r.rid += 1000                   # owner ids distinct per tenant
    trace = dense + moe
    reps = {on: _run_sp_churn_once(cfgs, params, trace, reload_bps,
                                   sharing=on)
            for on in (False, True)}
    shared = reps[True]
    dev = _sp_greedy_deviation(
        cfgs[SP_DENSE], params[SP_DENSE],
        [r for r in shared.completed if r.model_id == SP_DENSE])
    rows.append(_sp_pair_row(
        "serve_shared_prefix/churn", reps[False], shared,
        {"overlap": 0.5,
         "repartitions_shared": shared.repartitions,
         "greedy_dev": round(dev, 6)}))
    return rows


# --- fleet chaos scenario -------------------------------------------------------

# replicated pools behind the demand-placement router on a diurnal
# shifting-mix trace at 10x the single-pool volume; the chaos schedule
# degrades one replica's DMA clock, straggles another, then kills the
# primary mid-trace — the router must re-admit its tenants elsewhere
# with zero requests lost and bounded p99 queue age
FLEET_REPLICAS = 3
FLEET_N_REQUESTS = 10 * POOL_N_REQUESTS
FLEET_SMOKE_REQUESTS = POOL_N_REQUESTS
FLEET_CHAOS = "dma@10:r1x4/60,straggle@20:r2x3/60,kill@40:r0"
FLEET_SMOKE_CHAOS = "kill@5:r0"


def _fleet_row(rep, name: str) -> dict:
    return {
        "name": name,
        "requests": rep.n_requests,
        "completed": len(rep.completed),
        "shed": rep.requests_shed,
        "lost": rep.requests_lost,
        "new_tokens": rep.new_tokens,
        "tokens_per_step": round(rep.tokens_per_step, 3),
        "tokens_per_tick": round(rep.new_tokens / max(rep.ticks, 1), 3),
        "reload_bytes": rep.reload_bytes,
        "restream_bytes": rep.restream_bytes,
        "ticks": rep.ticks,
        "failovers": rep.failovers,
        "re_admissions": rep.re_admissions,
        "re_admission_latency_max": max(rep.re_admission_latency,
                                        default=0),
        "retries": rep.retries,
        "queue_age_p50": rep.queue_age_percentile(50),
        "queue_age_p99": rep.queue_age_percentile(99),
        "placement": {m: list(v) for m, v in sorted(rep.placement.items())},
        "per_replica": rep.per_replica,
    }


def run_fleet_chaos(smoke: bool = False) -> list[dict]:
    cfgs, params, tenants = _zoo()
    zoo = [(a, cfgs[a], share) for a, share in ZOO]
    n = FLEET_SMOKE_REQUESTS if smoke else FLEET_N_REQUESTS
    chaos_spec = FLEET_SMOKE_CHAOS if smoke else FLEET_CHAOS
    trace = diurnal_trace(
        tenants, n, mean_interarrival=MEAN_INTERARRIVAL,
        prompt_lens=(8, 16), gen_lens=(4, 8, 24), seed=3)
    reload_bps = calibrated_reload_bytes_per_step(
        (a, cfgs[a]) for a, _ in ZOO)
    pcfg = _pool_cfg(POOL_BUDGET_KIB, POOL_SLAB_FRAC, reload_bps)
    ecfg = PoolEngineConfig(
        num_slots=SLOTS, page_size=8, num_pages=97,
        max_pages_per_seq=16, prefill_bucket=8,
        policy="reload_aware", rr_quantum=16, stream="layer")

    rows = [{"name": "serve_fleet_setup", "replicas": FLEET_REPLICAS,
             "requests": n, "chaos": chaos_spec,
             "reload_bytes_per_step": reload_bps}]
    reps = {}
    for placement in ("demand", "mirror"):
        for label, spec in (("clean", ""), ("chaos", chaos_spec)):
            fcfg = FleetConfig(n_replicas=FLEET_REPLICAS,
                               placement=placement)
            faults = FaultSchedule.parse(spec) if spec else None
            fleet = FleetEngine(zoo, pcfg, ecfg, params, fcfg,
                                faults=faults)
            rep = fleet.run(copy.deepcopy(trace))
            reps[placement, label] = rep
            rows.append(_fleet_row(rep, f"serve_fleet/{placement}_{label}"))

    dc, mc = reps["demand", "clean"], reps["mirror", "clean"]
    rows.append({
        "name": "serve_fleet_placement",
        "tokens_per_step_ratio": round(
            dc.tokens_per_step / mc.tokens_per_step, 3),
        "tokens_per_tick_ratio": round(
            (dc.new_tokens / max(dc.ticks, 1))
            / (mc.new_tokens / max(mc.ticks, 1)), 3),
        "reload_bytes_saved": mc.reload_bytes - dc.reload_bytes,
        "same_tokens": _fleet_tokens(dc) == _fleet_tokens(mc),
    })
    dx = reps["demand", "chaos"]
    rows.append({
        "name": "serve_fleet_chaos",
        "lost_any": max(r.requests_lost for r in reps.values()),
        "failovers": dx.failovers,
        "re_admissions": dx.re_admissions,
        "re_admission_latency_max": max(dx.re_admission_latency,
                                        default=0),
        "shed": dx.requests_shed,
        "p99_queue_age_clean": dc.queue_age_percentile(99),
        "p99_queue_age_chaos": dx.queue_age_percentile(99),
        "p99_queue_age_factor": round(
            dx.queue_age_percentile(99)
            / max(dc.queue_age_percentile(99), 1.0), 3),
    })
    return rows


def _fleet_tokens(rep) -> dict:
    return {r.rid: tuple(r.generated) for r in rep.completed}


# --- decode wall scenario -------------------------------------------------------

# saturated dense decode with long generations: the steady state is pure
# decode on full slots, exactly what horizon fusion targets. The paired
# runs differ ONLY in the horizon (1 = legacy per-step dispatch), so the
# dispatch/sync/upload counters isolate the host-loop traffic the fusion
# removes. What that is worth in time is measured on the chip
# (chipbench), not here.
DW_SLOTS = 4
DW_N_REQUESTS = 8
DW_GEN_LENS = (48, 64)
DW_HORIZON = 32

# the DMA leg streams one tenant behind another's decode with the
# device-backed channel, so overlap is measured (async copy readiness)
# rather than modeled (ledger bytes)
DW_DMA_ZOO = (("codeqwen1.5-7b", 2.0), ("rwkv6-7b", 1.0))
DW_DMA_BUDGET_KIB = 700


def _dw_row(rep, name: str) -> dict:
    s = rep.summary()
    return {
        "name": name,
        "new_tokens": s["new_tokens"],
        "decode_steps": s["decode_steps"],
        "device_dispatches": s["device_dispatches"],
        "host_syncs": s["host_syncs"],
        "page_table_upload_bytes": s["page_table_upload_bytes"],
    }


def _dw_dma(smoke: bool) -> list[dict]:
    cfgs, params, tenants = {}, {}, []
    for arch, share in DW_DMA_ZOO:
        c = get_config(arch).reduced()
        cfgs[arch] = c
        params[arch] = get_model(c).init_params(c, jax.random.PRNGKey(0))
        tenants.append(dict(model_id=arch, vocab_size=c.vocab_size,
                            share=share))
    n = POOL_N_REQUESTS // 2 if smoke else POOL_N_REQUESTS
    trace = multi_tenant_trace(tenants, n,
                               mean_interarrival=MEAN_INTERARRIVAL,
                               prompt_lens=(8, 16), gen_lens=(4, 8, 24),
                               seed=7)
    reload_bps = calibrated_reload_bytes_per_step(
        (a, cfgs[a]) for a, _ in DW_DMA_ZOO)
    pcfg = PoolConfig(hbm_budget_bytes=DW_DMA_BUDGET_KIB << 10,
                      slab_frac=0.55, reload_bytes_per_step=reload_bps,
                      hysteresis_steps=8, device_dma=True)
    pool = ModelPool(pcfg)
    for arch, share in DW_DMA_ZOO:
        pool.register(arch, cfgs[arch], demand=share)
    pool.pack()
    ecfg = PoolEngineConfig(num_slots=DW_SLOTS, page_size=8, num_pages=49,
                            max_pages_per_seq=8, prefill_bucket=8,
                            policy="reload_aware", stream="layer")
    rep = PooledEngine(pool, params, ecfg).run(copy.deepcopy(trace))
    dma = pool.dma
    dma.check()
    return [{
        "name": "serve_decode_wall_dma",
        "copies_issued": dma.copies_issued,
        "measured_stall_steps": dma.measured_stall_steps,
        "modeled_stall_steps": rep.stall_steps,
        "measured_wait_s": round(dma.measured_wait_s, 4),
        "reload_bytes": rep.summary()["reload_bytes"],
    }]


def run_decode_wall(smoke: bool = False) -> list[dict]:
    cfg = get_config("codeqwen1.5-7b").reduced()
    params = get_model(cfg).init_params(cfg, jax.random.PRNGKey(0))
    # smoke trims requests, not generation length: the dispatch-ratio
    # claim is about the saturated steady state, which short gens never
    # reach past the admission transient
    n = 5 if smoke else DW_N_REQUESTS
    trace = poisson_trace(n, mean_interarrival=0.05,
                          prompt_lens=(8, 16), gen_lens=DW_GEN_LENS,
                          vocab_size=cfg.vocab_size, seed=5)
    # big pages so boundary clamps are rare; slots stay saturated
    base = dict(num_slots=DW_SLOTS, page_size=32, num_pages=33,
                max_pages_per_seq=4, prefill_bucket=32)
    reps = {}
    for label, h in (("per_step", 1), ("fused", DW_HORIZON)):
        ecfg = EngineConfig(horizon=h, **base)
        reps[label] = Engine(cfg, params, ecfg).run(copy.deepcopy(trace))
    ps, fu = reps["per_step"], reps["fused"]
    rows = [_dw_row(ps, "serve_decode_wall/per_step"),
            _dw_row(fu, "serve_decode_wall/fused")]

    rows.append({
        "name": "serve_decode_wall_fusion",
        "same_tokens": _pool_tokens(ps) == _pool_tokens(fu),
        "device_dispatch_ratio": round(
            ps.device_dispatches / max(fu.device_dispatches, 1), 3),
        "host_sync_ratio": round(
            ps.host_syncs / max(fu.host_syncs, 1), 3),
        "upload_bytes_ratio": round(
            ps.page_table_upload_bytes
            / max(fu.page_table_upload_bytes, 1), 3),
    })
    rows += _dw_dma(smoke)
    return rows


def run(scenario: str = "all", frontier: str = "full",
        smoke: bool = False, quant: str = "int8",
        reload_kib: int = 0, stream: str = "layer",
        slab_mode: str = "full") -> list[dict]:
    if smoke:                           # --smoke shrinks every scenario
        frontier = "smoke"
    rows = []
    if scenario in ("all", "engine_vs_static"):
        rows += run_engine_vs_static()
    if scenario in ("all", "multi_tenant"):
        rows += run_multi_tenant(frontier, quant=quant,
                                 reload_kib=reload_kib,
                                 stream=stream, slab_mode=slab_mode)
    if scenario in ("all", "shared_prefix"):
        rows += run_shared_prefix(smoke)
    if scenario in ("all", "fleet_chaos"):
        rows += run_fleet_chaos(smoke)
    if scenario in ("all", "decode_wall"):
        rows += run_decode_wall(smoke)
    return rows


def check(rows) -> None:
    speedups = [r for r in rows if r["name"].endswith("_speedup")
                and not r["name"].startswith("serve_pool")]
    if speedups:                        # engine_vs_static scenario present
        assert len(speedups) == len(ARCHS)
        for r in speedups:
            assert r["decode_tokens_per_step_ratio"] >= 2.0, \
                f"{r['name']}: engine only " \
                f"{r['decode_tokens_per_step_ratio']}x over static on " \
                "decode tokens/step"
            assert r["tokens_per_step_ratio"] > 1.0, \
                f"{r['name']}: engine not ahead once prefill compute " \
                f"is priced (ratio {r['tokens_per_step_ratio']})"
            if r["paged"] and "window_bounded" in r:
                # hybrid: the static ring is already O(window); the
                # paged claim is boundedness, not fewer bytes
                assert r["window_bounded"], \
                    f"{r['name']}: window ring exceeded its page bound"
            elif r["paged"]:
                assert r["kv_bytes_ratio"] > 1.0, \
                    f"{r['name']}: paged cache not smaller than dense " \
                    f"(ratio {r['kv_bytes_ratio']})"
        (err,) = [r["max_abs_err"] for r in rows
                  if r["name"] == "paged_attention_oracle"]
        assert err <= 1e-5, f"paged attention vs oracle: {err}"
    pool = [r for r in rows if r["name"] == "serve_pool_speedup"]
    if pool:                            # multi_tenant scenario present
        (r,) = pool
        assert r["families"] >= 5, "pool must serve >= 5 model families"
        assert r["same_tokens"], "policies must generate the same tokens"
        # hybrid + MoE tenants really flow through the pooled engine
        (ra_row,) = [x for x in rows
                     if x["name"] == "serve_pool_reload_aware"]
        for arch in ("recurrentgemma-9b", "deepseek-v2-lite-16b"):
            assert ra_row["model_tokens"].get(arch, 0) > 0, \
                f"{arch} generated no pooled tokens (static fallback?)"
        assert r["tokens_per_step_ratio"] > 1.0, \
            f"reload-aware not ahead on tokens/step " \
            f"(ratio {r['tokens_per_step_ratio']})"
        assert r["reload_bytes_saved"] > 0, \
            "reload-aware must move strictly fewer weight-reload bytes"
        # layer-granular overlapped streaming at equal HBM budget
        (ov,) = [x for x in rows if x["name"] == "serve_pool_overlap"]
        assert ov["same_tokens"], "streams must generate the same tokens"
        assert ov["stall_steps_layer"] < ov["stall_steps_model"], \
            "overlapped streaming must strictly reduce stall steps"
        assert ov["tokens_per_step_ratio"] > 1.0, \
            f"overlapped streaming not ahead on tokens/step " \
            f"(ratio {ov['tokens_per_step_ratio']})"
        assert len(ov["families_with_fewer_stalls"]) >= 2, \
            f"stall reduction only in {ov['families_with_fewer_stalls']}"
        assert len(ov["families_with_better_tokens_per_step"]) >= 2, \
            "tokens/step gain must cover >= 2 families"
        # load-driven repartitioning on the shifting mix: epoch mode must
        # not lose throughput to the static partition, and must really
        # have moved pages with clean arena invariants (the run asserts
        # conservation/disjointness/ceiling at every epoch internally)
        (rp,) = [x for x in rows if x["name"] == "serve_pool_repartition"]
        assert rp["same_tokens"], \
            "repartition modes must generate the same tokens"
        assert rp["tokens_per_step_ratio"] >= 1.0, \
            f"epoch repartitioning behind the static partition " \
            f"(ratio {rp['tokens_per_step_ratio']})"
        assert rp["repartitions"] > 0 and rp["pages_moved"] > 0, \
            "shifting mix never triggered a lease move"
        frontier = [x for x in rows
                    if x["name"].startswith("serve_pool_frontier/")]
        assert frontier, "budget x slab frontier rows missing"
        for f in frontier:              # overlap never loses stall steps
            if f["stream"] == "layer" and f["slab_mode"] == "full":
                twin = next(x for x in frontier
                            if x["budget_kib"] == f["budget_kib"]
                            and x["slab_frac"] == f["slab_frac"]
                            and x["stream"] == "model")
                assert f["stall_steps"] <= twin["stall_steps"], \
                    f"{f['name']}: layer streaming stalled more"
        # bounded slab at the tightest frontier point: the 2-slice double
        # buffer must make at least one more tenant servable (and really
        # serve it), paying for the extra tenant ONLY with that tenant's
        # own DMA-bound re-stream steps — the incumbents' stall steps
        # must not increase. (Total stalls CAN grow: a tenant whose
        # working set exceeds the slab is served at the DMA's rate, and
        # once the rest of the trace drains, its re-stream waits have
        # nothing to hide behind; in full mode that tenant is simply
        # refused, which is the alternative being measured.)
        bmin = min(f["budget_kib"] for f in frontier)
        smin = min(f["slab_frac"] for f in frontier
                   if f["budget_kib"] == bmin)
        point = {f["slab_mode"]: f for f in frontier
                 if f["budget_kib"] == bmin and f["slab_frac"] == smin
                 and f["stream"] == "layer"}
        full_srv = set(point["full"]["servable_models"])
        newly = set(point["bounded"]["servable_models"]) - full_srv
        assert len(newly) >= 1, \
            f"bounded slab hosts no extra tenant at b{bmin}_s{smin}"
        assert point["bounded"]["new_tokens"] \
            > point["full"]["new_tokens"], \
            "the newly servable tenant generated nothing"
        for mode, f in point.items():
            inc = sum(f["stall_steps_by_model"][m] for m in full_srv)
            point[mode] = (f, inc)
        assert point["bounded"][1] <= point["full"][1], \
            f"bounded slab increased the incumbents' stalls at " \
            f"b{bmin}_s{smin}: {point['bounded'][1]} vs {point['full'][1]}"
        assert point["bounded"][0]["restream_bytes"] > 0, \
            "bounded slab never re-streamed (the trade is not exercised)"
        # compressed weight streaming: quantized slices must shrink the
        # planned reload set and the double-buffer pairs by the encoding
        # ratio (int8 payload is exactly 1/2 + per-channel scales, hence
        # the 1.9 floor; int4 packs two rows per byte), without changing
        # a single generated token at the base budget.
        qsp = [x for x in rows if x["name"] == "serve_pool_quant_speedup"]
        (qs,) = qsp
        # auto's floor equals int8's: the reduced configs keep so few
        # layers that the sensitivity policy (embed/head/first/last at
        # int8) can cover a whole model; its gain over int8 — interior
        # and expert slices at int4 — is asserted as an ordering below
        plan_floor = {"int8": 1.9, "int4": 3.5, "auto": 1.9}
        for qm, m in qs["modes"].items():
            floor = plan_floor[qm]
            assert m["plan_reload_ratio"] >= floor, \
                f"quant {qm}: planned reload bytes only " \
                f"{m['plan_reload_ratio']}x smaller (need {floor}x)"
            assert m["double_buffer_ratio"] >= floor, \
                f"quant {qm}: double-buffer slab only " \
                f"{m['double_buffer_ratio']}x smaller (need {floor}x)"
            if qs["stream"] == "layer" and qs["slab_mode"] == "full":
                assert m["same_tokens"], \
                    f"quant {qm}: streamed quantization changed the " \
                    "generated tokens (byte accounting must not leak " \
                    "into decode math)"
                assert m["stall_steps"] <= qs["stall_steps_off"], \
                    f"quant {qm}: fewer reload bytes but MORE stalls " \
                    f"({m['stall_steps']} vs {qs['stall_steps_off']})"
        if {"int8", "int4", "auto"} <= set(qs["modes"]):
            i8, i4, au = (qs["modes"][k]["plan_reload_ratio"]
                          for k in ("int8", "int4", "auto"))
            assert i8 <= au <= i4, \
                f"auto policy not between int8 and int4: {i8}/{au}/{i4}"
        # the PR-5 flip point: compression moves >= 1 tenant's working
        # set INSIDE the slab, so full-mode servability flips without
        # paying the bounded restream tax — and in bounded mode the
        # restream traffic (charged per decode burst) collapses.
        qf = {(x["quant"], x["slab_mode"]): x for x in rows
              if x["name"].startswith("serve_pool_quant_frontier/")}
        qon = next(qm for qm in qs["modes"] if (qm, "full") in qf)
        off_full, on_full = qf[("off", "full")], qf[(qon, "full")]
        off_srv = set(off_full["servable_models"])
        flipped = set(on_full["servable_models"]) - off_srv
        assert len(flipped) >= 1, \
            f"quant {qon}: no additional tenant became servable at the " \
            "tightest frontier point"
        assert on_full["new_tokens"] > off_full["new_tokens"], \
            f"quant {qon}: the newly servable tenant generated nothing"
        off_b, on_b = qf[("off", "bounded")], qf[(qon, "bounded")]
        assert on_b["restream_bytes"] < off_b["restream_bytes"], \
            f"quant {qon}: bounded restream traffic did not shrink " \
            f"({on_b['restream_bytes']} vs {off_b['restream_bytes']})"
        off_moved = off_b["reload_bytes"] + off_b["restream_bytes"]
        on_moved = on_b["reload_bytes"] + on_b["restream_bytes"]
        assert off_moved / max(on_moved, 1) >= 2.0, \
            f"quant {qon}: bounded-mode DMA traffic only " \
            f"{off_moved / max(on_moved, 1):.2f}x smaller (need 2x: " \
            "compression should also collapse the restream tax)"
    sp = sorted((r for r in rows
                 if r["name"].startswith("serve_shared_prefix/o")),
                key=lambda r: r["overlap"])
    for r in sp:                        # capacity pairs
        assert r["same_tokens"], \
            f"{r['name']}: sharing changed decode output " \
            "(must be token-for-token equal to the unshared oracle)"
        assert r["shared_page_hits"] > 0, \
            f"{r['name']}: no page was ever admitted by reference"
        assert r["prefill_tokens_shared"] < r["prefill_tokens_base"], \
            f"{r['name']}: prefill compute did not drop"
        if r["overlap"] >= 0.5:
            assert r["kv_peak_shared"] < r["kv_peak_base"], \
                f"{r['name']}: peak KV demand bytes did not drop"
            # superlinear: the joint compute x capacity saving beats
            # the linear share of the overlap
            assert r["product_saved_frac"] > r["overlap"], \
                f"{r['name']}: joint saving {r['product_saved_frac']} " \
                f"not superlinear in overlap {r['overlap']}"
    for lo, hi in zip(sp, sp[1:]):      # savings grow with overlap
        assert hi["prefill_saved_frac"] > lo["prefill_saved_frac"], \
            f"prefill saving not increasing: {lo['name']} -> " \
            f"{hi['name']}"
    churn = [r for r in rows if r["name"] == "serve_shared_prefix/churn"]
    if churn:
        (c,) = churn
        assert c["greedy_dev"] <= SP_GREEDY_TOL, \
            f"churn run tokens deviate {c['greedy_dev']} from the " \
            "teacher-forced greedy oracle: shared/CoW pages corrupted"
        assert c["cow_copies"] > 0, \
            "no divergence write ever copied a shared page " \
            "(the CoW path went unexercised)"
        assert c["shared_page_hits"] > 0, \
            "churn run never admitted a page by reference"
        assert c["preemptions_shared"] > 0, \
            "the tight page budget never forced a preempt"
        assert c["repartitions_shared"] > 0, \
            "epoch repartitioning never ran " \
            "(invariants not exercised across lease moves)"
        assert c["prefill_tokens_shared"] < c["prefill_tokens_base"], \
            "churn run prefill compute did not drop"
    fleet = [r for r in rows if r["name"] == "serve_fleet_placement"]
    if fleet:                           # fleet_chaos scenario present
        (fp,) = fleet
        assert fp["same_tokens"], \
            "placements must generate the same tokens per request"
        assert fp["tokens_per_step_ratio"] > 1.0, \
            f"demand placement not ahead of mirror on fleet tokens/step " \
            f"(ratio {fp['tokens_per_step_ratio']})"
        assert fp["reload_bytes_saved"] > 0, \
            "demand placement must move strictly fewer reload bytes " \
            "than the mirror baseline"
        (fc,) = [x for x in rows if x["name"] == "serve_fleet_chaos"]
        assert fc["lost_any"] == 0, \
            f"{fc['lost_any']} requests lost under chaos"
        assert fc["failovers"] >= 1, "the kill never landed"
        assert fc["re_admissions"] >= 1, \
            "the killed replica carried no work to re-admit"
        assert fc["p99_queue_age_factor"] <= 10.0, \
            f"chaos p99 queue age unbounded " \
            f"(factor {fc['p99_queue_age_factor']})"
    dw = [r for r in rows if r["name"] == "serve_decode_wall_fusion"]
    if dw:                              # decode_wall scenario present
        (d,) = dw
        assert d["same_tokens"], \
            "horizon fusion changed the generated tokens (must be " \
            "token-for-token equal to the per-step dispatch)"
        assert d["device_dispatch_ratio"] >= 5.0, \
            f"fused decode only cut device dispatches " \
            f"{d['device_dispatch_ratio']}x (need 5x)"
        assert d["host_sync_ratio"] >= 5.0, \
            f"fused decode only cut host syncs " \
            f"{d['host_sync_ratio']}x (need 5x)"
        assert d["upload_bytes_ratio"] > 1.0, \
            "fused decode shipped at least as many page-table bytes"
        (dd,) = [x for x in rows if x["name"] == "serve_decode_wall_dma"]
        assert dd["copies_issued"] > 0, \
            "the device DMA channel never issued a real copy"
        assert dd["measured_stall_steps"] <= dd["modeled_stall_steps"], \
            f"measured DMA stalls ({dd['measured_stall_steps']}) " \
            f"exceed the modeled ledger ({dd['modeled_stall_steps']}): " \
            "the async copy is not overlapping"


if __name__ == "__main__":
    import argparse
    import json

    ap = argparse.ArgumentParser()
    ap.add_argument("--scenario", default="all",
                    choices=("all", "engine_vs_static", "multi_tenant",
                             "shared_prefix", "fleet_chaos",
                             "decode_wall"))
    ap.add_argument("--frontier", default="full",
                    choices=("full", "smoke"),
                    help="budget x slab sweep size (smoke: one point, "
                         "for CI)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI size: frontier at one point, fleet_chaos "
                         "at 1x volume with a single kill, quant axis "
                         "at off + --quant only")
    add_streaming_args(ap)     # shared with launch.serve: --quant etc.
    args = ap.parse_args()
    rows = run(args.scenario, args.frontier, args.smoke,
               quant=args.quant, reload_kib=args.reload_kib_per_step,
               stream=args.stream, slab_mode=args.slab_mode)
    for r in rows:
        print(json.dumps(r))
    check(rows)
    print("ok")
