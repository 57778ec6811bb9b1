"""Size a configuration's KV page budget from compiles for a described v5e.

    JAX_PLATFORMS=cpu python chipbench/size_kv.py olmo-1b

Compiles, for one chip of a described ``v5e:2x2`` and with no chip
attached, the programs a cell runs at the configuration's full size: the
fused decode step at the configuration's slots and the prefill of the
longest prompt the engine can be asked to prefill (a re-admission after
preemption prefills prompt plus output, up to the maximum context). It
prints each program's memory analysis and the budget left for KV pages.

A program's temporaries grow with the page pool it is handed (the decode
step keeps copies of the pool), so each program is compiled at two pool
sizes and its temporaries fitted as ``a + b * pool``. The budget is the
largest pool for which every program fits:

    weights + pool + a + b * pool <= HBM - reserved - margin

with the HBM and the runtime's reservation as the TPU compiler reports them
for one v5e, and the margin the benchmark keeps for the engine's small
programs, the loop state and fragmentation. The Pallas kernels are what the
chip runs, so ``ops._on_tpu`` is steered to true here; nothing runs and no
time is measured.
"""

from __future__ import annotations

import json
import os
import sys
from functools import partial
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

HBM_BYTES = 15.75 * 2**30      # one v5e chip, as its compiler counts it
RESERVED_BYTES = 258 * 2**20    # the runtime's reservation, likewise
MARGIN_BYTES = 2**30
POOLS = (1000, 2000)            # pages at which the programs are compiled


def compile_programs(cfg, spec: dict, num_slots: int, n_pages: int) -> dict:
    """name -> compiled program, for one described v5e chip."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from chipbench import bench
    from repro.models import get_model
    from repro.runtime import EngineConfig

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    dev = SingleDeviceSharding(topo.devices[0])
    api = get_model(cfg)

    def sds(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=dev),
            tree)

    params = sds(jax.eval_shape(partial(api.init_params, cfg),
                                jax.random.PRNGKey(0)))
    page = EngineConfig().page_size
    hmax = EngineConfig().horizon
    bucket = EngineConfig().prefill_bucket
    max_ctx = spec["serving"]["max_context"]
    m = -(-max_ctx // page) + 1
    vec = jax.ShapeDtypeStruct((num_slots,), jnp.int32, sharding=dev)
    mask = jax.ShapeDtypeStruct((num_slots,), bool, sharding=dev)
    h = jax.ShapeDtypeStruct((), jnp.int32, sharding=dev)
    out = {}
    if cfg.family == "ssm":
        from repro.models import rwkv6 as R
        state = sds(jax.eval_shape(
            lambda: api.init_decode_state(cfg, num_slots)))

        def decode(params, state, pending, lengths, remaining, mask, h):
            return R.decode_multi(cfg, params, state, pending, lengths,
                                  remaining, mask, h, hmax=hmax)

        out["decode"] = jax.jit(decode, donate_argnums=(1, 2, 3, 4)).lower(
            params, state, vec, vec, vec, mask, h).compile()
        longest = max(bench.load_traffic(ROOT, "chat")["prompt"]["snap_up"])
        toks = jax.ShapeDtypeStruct((1, longest), jnp.int32, sharding=dev)
        out["prefill"] = jax.jit(
            lambda p, b: api.prefill(cfg, p, b, 0)).lower(
            params, {"tokens": toks}).compile()
    else:
        from repro.models import transformer as T
        state = sds(jax.eval_shape(
            lambda: T.init_paged_decode_state(cfg, n_pages, page)))
        table = jax.ShapeDtypeStruct((num_slots, m), jnp.int32, sharding=dev)

        def decode(params, state, pending, lengths, remaining, table, mask,
                   h):
            return T.paged_decode_multi(cfg, params, state, pending,
                                        lengths, remaining, table, mask, h,
                                        hmax=hmax)

        out["decode"] = jax.jit(decode, donate_argnums=(1, 2, 3, 4)).lower(
            params, state, vec, vec, vec, table, mask, h).compile()
        longest = -(-max_ctx // bucket) * bucket

        def prefill(params, state, batch, lengths, page_ids):
            last, (k, v) = T.paged_prefill(cfg, params, batch, lengths)
            return last[0], T.write_prefill_pages(
                cfg, state, (k[:, 0], v[:, 0]), page_ids)

        toks = jax.ShapeDtypeStruct((1, longest), jnp.int32, sharding=dev)
        one = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=dev)
        pids = jax.ShapeDtypeStruct((longest // page,), jnp.int32,
                                    sharding=dev)
        out["prefill"] = jax.jit(prefill, donate_argnums=(1,)).lower(
            params, state, {"tokens": toks}, one, pids).compile()
        assert "tpu_custom_call" in out["decode"].as_text()
    return out


def main(name: str) -> None:
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    from chipbench import bench
    from repro.kernels import ops
    from repro.models import get_model
    from repro.runtime.kv_pager import PagerConfig

    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    ops._on_tpu = lambda: True
    spec = bench.load_config(ROOT, name)
    cfg = bench.model_config(spec)
    slots = spec["serving"]["num_slots"]
    shapes = jax.eval_shape(partial(get_model(cfg).init_params, cfg),
                            jax.random.PRNGKey(0))
    weights = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(shapes))
    paged = cfg.family != "ssm"
    page_bytes = PagerConfig(2, 16, 2).page_bytes(cfg) if paged else 0
    room = HBM_BYTES - RESERVED_BYTES - MARGIN_BYTES - weights
    fits, report = [], {}
    for n in POOLS if paged else (0,):
        for k, c in compile_programs(cfg, spec, slots, n).items():
            ma = c.memory_analysis()
            report.setdefault(k, []).append(
                {"pool_bytes": n * page_bytes,
                 "argument": ma.argument_size_in_bytes,
                 "output": ma.output_size_in_bytes,
                 "temp": ma.temp_size_in_bytes})
    for k, rows in report.items():
        if not paged:
            fits.append(room - rows[0]["argument"] + weights
                        - rows[0]["temp"])
            continue
        (p1, t1), (p2, t2) = ((r["pool_bytes"], r["temp"]) for r in rows)
        b = (t2 - t1) / (p2 - p1)
        a = t1 - b * p1
        report[k].append({"temp_fit": [a, b]})
        fits.append((room - a) / (1 + b))
    print(json.dumps({"config": name, "num_slots": slots,
                      "weight_bytes": weights, "programs": report,
                      "hbm_bytes": HBM_BYTES, "reserved_bytes": RESERVED_BYTES,
                      "margin_bytes": MARGIN_BYTES,
                      ("kv_budget_bytes" if paged else "spare_bytes"):
                      int(min(fits))}, indent=1))


if __name__ == "__main__":
    main(sys.argv[1])
