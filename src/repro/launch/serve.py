"""Serving driver: continuous-batching engine, static batch, or model pool.

``--mode engine`` runs the runtime.Engine — admission queue, per-slot
request state, paged KV cache, slot recycling — against a mixed-length
Poisson arrival trace. ``--mode static`` is the seed lockstep path kept
as the measurable baseline: one batch prefills together, decodes in
unison, and holds a dense cache_len x batch KV cache. ``--mode auto``
picks the engine when the model config has a backend (dense / vlm / ssm /
hybrid / MLA-MoE) and falls back to static otherwise (whisper's enc-dec,
and GQA-MoE olmoe whose cache is not latent-compressed). ``--mode pool`` serves a whole model
zoo (``--zoo arch[:share],..``) from one shared HBM budget: the
runtime.ModelPool bin-packs each model's weights as resident / streamed /
evicted and the PooledEngine charges weight reloads when cold models
activate (``--policy reload_aware`` or the naive ``round_robin`` swap
baseline). ``--stream layer`` (default) streams a cold model's per-layer
schedule behind other tenants' decode steps — double-buffered prefetch,
stalls only on prefetch misses — while ``--stream model`` charges the
whole reload serially up front; the reload clock defaults to the
roofline-calibrated DMA bandwidth (``--reload-kib-per-step 0``). The
device-memory arena (runtime.arena) owns the modeled budget:
``--repartition epoch`` moves free KV pages between tenants after
live-page watermarks every ``--epoch-steps``; ``--slab-mode bounded``
serves slab-overflow models from a 2-slice double buffer (re-streamed
per decode burst); ``--max-bypass`` caps how long a page-starved head
can be bypassed by neighbours; ``--shifting-mix`` reverses the zoo's
traffic shares mid-trace (the repartition stress shape). ``--mode fleet`` replicates the pool
``--replicas`` times behind the demand-placement router (runtime.fleet):
each model lands on a subset of replicas by reuse-per-byte, requests
route with tenant affinity + least-loaded fallback, and ``--chaos``
injects replica kills / degraded DMA clocks / stragglers from a
deterministic FaultSchedule — a killed replica's tenants are re-admitted
elsewhere with zero requests lost.

Serves on a 1x1 mesh: one TPU chip, or the CPU at ``reduced()`` size;
``--full`` takes the published config (olmo-1b fits one v5e chip). The
pod-mesh serving cells are proven by the dry-run.

  PYTHONPATH=src python -m repro.launch.serve --arch codeqwen1.5-7b \
      --batch 4 --prompt-len 32 --gen 16
  PYTHONPATH=src python -m repro.launch.serve --arch olmo-1b --full \
      --mode engine --batch 8 --prompt-len 128 --gen 64
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import get_config
from ..models import get_model
from ..runtime import (Engine, EngineConfig, ModelPool, PoolConfig,
                       PoolEngineConfig, PooledEngine,
                       calibrated_reload_bytes_per_step, engine_backend,
                       multi_tenant_trace, poisson_trace,
                       shifting_mix_trace, vlm_extras_fn)
from . import sharding as sh
from .cli import add_streaming_args
from .compile_cache import use_compile_cache
from .mesh import make_host_mesh, make_production_mesh
from .steps import make_prefill_step, make_serve_step


def run_static(cfg, params, args):
    """Seed lockstep path: one prefill, ``--gen`` decode steps in unison."""
    key = jax.random.PRNGKey(args.seed)
    cache_len = args.cache_len or (args.prompt_len + args.gen)

    key, kt = jax.random.split(key)
    batch = {"tokens": jax.random.randint(
        kt, (args.batch, args.prompt_len), 0, cfg.vocab_size)}
    if cfg.family == "encdec":
        batch["frames"] = jax.random.normal(
            kt, (args.batch, cfg.encoder.seq_len, cfg.d_model))
    if cfg.family == "vlm":
        batch["patch_embeds"] = jax.random.normal(
            kt, (args.batch, 4, cfg.d_model))

    prefill = jax.jit(make_prefill_step(cfg, cache_len))
    serve = jax.jit(make_serve_step(cfg), donate_argnums=(1,))

    t0 = time.monotonic()
    logits, state = jax.block_until_ready(prefill(params, batch))
    t_prefill = time.monotonic() - t0

    toks = []
    key, ks = jax.random.split(key)
    tok = jax.random.categorical(ks, logits / args.temperature, -1)
    t0 = time.monotonic()
    for _ in range(args.gen):
        toks.append(np.asarray(tok))
        logits, state = serve(params, state, tok)
        key, ks = jax.random.split(key)
        tok = jax.random.categorical(ks, logits / args.temperature, -1)
    jax.block_until_ready(logits)
    t_decode = (time.monotonic() - t0) / args.gen

    out = np.stack(toks, axis=1)
    print(f"arch={cfg.name} mode=static batch={args.batch} "
          f"prompt={args.prompt_len} gen={args.gen}")
    print(f"prefill: {t_prefill * 1e3:.1f} ms   "
          f"decode: {t_decode * 1e3:.1f} ms/token")
    for b in range(min(args.batch, 2)):
        print(f"  seq{b}: {out[b].tolist()}")
    assert np.isfinite(np.asarray(logits)).all()
    print("ok")
    return 0


def run_engine(cfg, params, args):
    """Continuous batching against a Poisson arrival trace."""
    page = max(8, args.prompt_len // 4)
    max_len = args.prompt_len + args.gen
    pages_per_seq = -(-max_len // page) + 1
    ecfg = EngineConfig(
        num_slots=args.batch, page_size=page,
        num_pages=1 + pages_per_seq * args.batch * 2,
        max_pages_per_seq=pages_per_seq,
        prefill_bucket=page,
        greedy=False, temperature=args.temperature, seed=args.seed)
    extras_fn = vlm_extras_fn(cfg) if cfg.family == "vlm" else None
    trace = poisson_trace(
        args.requests, mean_interarrival=args.mean_interarrival,
        prompt_lens=(max(args.prompt_len // 2, 1), args.prompt_len),
        gen_lens=(max(args.gen // 4, 1), max(args.gen // 2, 1), args.gen),
        vocab_size=cfg.vocab_size, seed=args.seed, extras_fn=extras_fn)
    rep = Engine(cfg, params, ecfg).run(trace)
    print(f"arch={cfg.name} mode=engine slots={args.batch} "
          f"requests={args.requests}")
    print(json.dumps(rep.summary(), indent=1))
    done = [r for r in rep.completed if not r.truncated]
    for r in done[:2]:
        print(f"  req{r.rid}: {r.generated}")
    assert done, "no requests completed"
    print("ok")
    return 0


def init_sharded_params(cfg, mesh, seed: int):
    """Seeded random parameters, placed on ``mesh`` by the sharding rules."""
    params = get_model(cfg).init_params(cfg, jax.random.PRNGKey(seed))
    p_spec = sh.param_pspecs(params, mesh)
    return jax.device_put(params, sh.to_shardings(p_spec, mesh))


def parse_zoo(spec: str) -> list[tuple[str, float]]:
    """``arch[:share],arch[:share],..`` -> [(arch_id, traffic share)]."""
    out = []
    for item in spec.split(","):
        arch, _, share = item.strip().partition(":")
        out.append((arch, float(share) if share else 1.0))
    return out


def run_pool(args):
    """Multi-tenant serving: a model zoo bin-packed into one HBM pool."""
    zoo, cfgs, params, tenants, pcfg = _zoo_setup(args)
    pool = ModelPool(pcfg)
    for arch, share in zoo:
        pool.register(arch, cfgs[arch], demand=share)
    plan = pool.pack()
    print(json.dumps(plan.summary(), indent=1))

    page = max(8, args.prompt_len // 4)
    max_len = args.prompt_len + args.gen
    pages_per_seq = -(-max_len // page) + 1
    ecfg = PoolEngineConfig(
        num_slots=args.batch, page_size=page,
        num_pages=1 + pages_per_seq * args.batch * 2,
        max_pages_per_seq=pages_per_seq, prefill_bucket=page,
        greedy=False, temperature=args.temperature, seed=args.seed,
        policy=args.policy, rr_quantum=args.rr_quantum,
        stream=args.stream, repartition=args.repartition,
        epoch_steps=args.epoch_steps,
        max_bypass_steps=args.max_bypass)
    trace_fn = shifting_mix_trace if args.shifting_mix \
        else multi_tenant_trace
    trace = trace_fn(
        tenants, args.requests, mean_interarrival=args.mean_interarrival,
        prompt_lens=(max(args.prompt_len // 2, 1), args.prompt_len),
        gen_lens=(max(args.gen // 4, 1), max(args.gen // 2, 1), args.gen),
        seed=args.seed)
    eng = PooledEngine(pool, params, ecfg)
    rep = eng.run(trace)
    print(f"zoo={args.zoo} mode=pool policy={args.policy} "
          f"stream={args.stream} slab_mode={args.slab_mode} "
          f"repartition={args.repartition} slots={args.batch} "
          f"requests={args.requests}")
    print(json.dumps(rep.summary(), indent=1))
    print(json.dumps({"arena": eng.arena.summary()}, indent=1))
    done = [r for r in rep.completed if not r.truncated]
    for r in done[:3]:
        print(f"  req{r.rid} [{r.model_id}]: {r.generated}")
    assert done, "no requests completed"
    print("ok")
    return 0


def _zoo_setup(args):
    """Shared pool/fleet zoo construction: configs, params, tenants, and
    the auto-sized PoolConfig."""
    zoo = parse_zoo(args.zoo)
    cfgs, params, tenants = {}, {}, []
    for arch, share in zoo:
        cfg = get_config(arch).reduced() if not args.full \
            else get_config(arch)
        cfgs[arch] = cfg
        params[arch] = get_model(cfg).init_params(
            cfg, jax.random.PRNGKey(args.seed))
        tenants.append(dict(
            model_id=arch, vocab_size=cfg.vocab_size, share=share,
            extras_fn=vlm_extras_fn(cfg) if cfg.family == "vlm" else None))
    from ..runtime.model_pool import model_weight_bytes
    weights = {a: model_weight_bytes(c) for a, c in cfgs.items()}
    # auto budget: pin ~62% of the zoo, slab big enough for the largest
    # working set (so every registered model stays servable)
    s = args.slab_frac
    if not 0.0 < s < 1.0:
        raise SystemExit("--slab-frac must be in (0, 1)")
    budget = args.hbm_budget_kib * 1024 or 1024 + int(max(
        0.62 * sum(weights.values()) / (1.0 - s),
        max(weights.values()) / s))
    # 0 -> the roofline-calibrated DMA clock (one clock with the kernel
    # benches: an engine step is a decode step, reloads cross the slow
    # DRAM->HBM interface); fallback=0 distinguishes "no roofline
    # artifacts found" from a genuine calibration
    reload_bps, label = args.reload_kib_per_step * 1024, ""
    if not reload_bps:
        reload_bps = calibrated_reload_bytes_per_step(cfgs.items(),
                                                      fallback=0)
        label = " (roofline-calibrated)"
        if not reload_bps:
            reload_bps = 8 * 1024
            label = " (uncalibrated default: no roofline artifacts found)"
    print(f"reload clock: {reload_bps} B/step{label}")
    pcfg = PoolConfig(hbm_budget_bytes=budget, slab_frac=s,
                      reload_bytes_per_step=reload_bps,
                      hysteresis_steps=args.hysteresis,
                      slab_mode=args.slab_mode,
                      quant=args.quant)
    return zoo, cfgs, params, tenants, pcfg


def run_fleet(args):
    """Replicated pools behind the demand-placement router, with
    optional chaos injection (``--chaos "kill@120:r1,dma@200:r0x4/100"``)."""
    from ..runtime import (FaultSchedule, FleetConfig, FleetEngine,
                           diurnal_trace)
    zoo, cfgs, params, tenants, pcfg = _zoo_setup(args)

    page = max(8, args.prompt_len // 4)
    max_len = args.prompt_len + args.gen
    pages_per_seq = -(-max_len // page) + 1
    ecfg = PoolEngineConfig(
        num_slots=args.batch, page_size=page,
        num_pages=1 + pages_per_seq * args.batch * 2,
        max_pages_per_seq=pages_per_seq, prefill_bucket=page,
        greedy=False, temperature=args.temperature, seed=args.seed,
        policy=args.policy, rr_quantum=args.rr_quantum,
        stream=args.stream, repartition=args.repartition,
        epoch_steps=args.epoch_steps,
        max_bypass_steps=args.max_bypass)
    fcfg = FleetConfig(n_replicas=args.replicas,
                       placement=args.placement)
    faults = FaultSchedule.parse(args.chaos) if args.chaos else None
    trace = diurnal_trace(
        tenants, args.requests, mean_interarrival=args.mean_interarrival,
        prompt_lens=(max(args.prompt_len // 2, 1), args.prompt_len),
        gen_lens=(max(args.gen // 4, 1), max(args.gen // 2, 1), args.gen),
        seed=args.seed)
    fleet = FleetEngine([(a, cfgs[a], sh_) for a, sh_ in zoo],
                        pcfg, ecfg, params, fcfg, faults=faults)
    rep = fleet.run(trace)
    print(f"zoo={args.zoo} mode=fleet replicas={args.replicas} "
          f"placement={args.placement} chaos={args.chaos or 'none'} "
          f"requests={args.requests}")
    print(json.dumps(rep.summary(), indent=1))
    assert rep.requests_lost == 0
    assert rep.completed, "no requests completed"
    print("ok")
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="codeqwen1.5-7b")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--mesh", default="host", choices=("host", "pod"))
    ap.add_argument("--mode", default="auto",
                    choices=("auto", "engine", "static", "pool", "fleet"))
    ap.add_argument("--replicas", type=int, default=2,
                    help="fleet mode: number of replicated pools")
    ap.add_argument("--placement", default="demand",
                    choices=("demand", "mirror"),
                    help="fleet model placement: 'demand' packs copies "
                         "by reuse-per-byte, 'mirror' puts every model "
                         "on every replica that fits (static baseline)")
    ap.add_argument("--chaos", default="",
                    help="fleet fault schedule, e.g. "
                         "'kill@120:r1,dma@200:r0x4/100,straggle@300:r2x3/50'")
    ap.add_argument("--zoo",
                    default="codeqwen1.5-7b:2,qwen2-vl-7b:1,rwkv6-7b:1,"
                            "recurrentgemma-9b:1,deepseek-v2-lite-16b:1",
                    help="pool mode model-zoo spec: arch[:share],..")
    ap.add_argument("--policy", default="reload_aware",
                    choices=("reload_aware", "round_robin"))
    add_streaming_args(ap)          # --stream/--slab-mode/--reload-kib/--quant
    ap.add_argument("--repartition", default="off",
                    choices=("off", "epoch"),
                    help="KV page leases: 'off' freezes the init-time "
                         "partition, 'epoch' follows per-tenant "
                         "live-page watermarks every --epoch-steps")
    ap.add_argument("--epoch-steps", type=int, default=64,
                    help="steps between arena repartition epochs")
    ap.add_argument("--max-bypass", type=int, default=64,
                    help="admission aging bound: max steps a page-"
                         "starved head can be bypassed (0 = unbounded)")
    ap.add_argument("--shifting-mix", action="store_true",
                    help="reverse the zoo's traffic shares mid-trace "
                         "(the repartition stress shape)")
    ap.add_argument("--hbm-budget-kib", type=int, default=0,
                    help="pool HBM budget (0 -> auto-size from the zoo)")
    ap.add_argument("--slab-frac", type=float, default=0.5,
                    help="pool budget fraction reserved for weight swaps")
    ap.add_argument("--hysteresis", type=int, default=32,
                    help="min steps a model stays hot before eviction")
    ap.add_argument("--rr-quantum", type=int, default=16,
                    help="round_robin steps per tenant turn")
    ap.add_argument("--batch", type=int, default=4,
                    help="static batch size / engine slot count")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--cache-len", type=int, default=0)
    ap.add_argument("--requests", type=int, default=0,
                    help="engine trace length (default 3x slots)")
    ap.add_argument("--mean-interarrival", type=float, default=0.5,
                    help="engine trace mean gap in decode steps")
    ap.add_argument("--temperature", type=float, default=0.8)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if not args.requests:
        args.requests = 3 * args.batch

    use_compile_cache()
    mesh = (make_production_mesh if args.mesh == "pod"
            else make_host_mesh)()
    if args.mode == "pool":
        with mesh:
            return run_pool(args)
    if args.mode == "fleet":
        with mesh:
            return run_fleet(args)
    cfg = get_config(args.arch)
    if not args.full:
        cfg = cfg.reduced()
    mode = args.mode
    if mode == "auto":
        mode = "engine" if engine_backend(cfg) else "static"

    with mesh:
        params = init_sharded_params(cfg, mesh, args.seed)
        if mode == "engine":
            return run_engine(cfg, params, args)
        return run_static(cfg, params, args)


if __name__ == "__main__":
    raise SystemExit(main())
