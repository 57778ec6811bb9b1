"""Count, on the CPU, how many slots a paged configuration's KV budget holds
under a traffic mix without preemption.

    JAX_PLATFORMS=cpu python chipbench/size_slots.py olmo-1b chat \\
        --slots 16,20,24 --seeds 1,2,3

Replays ``runtime.Engine.run`` with the configuration's page geometry (page
size, table width and the page count its ``kv_budget_bytes`` buys at full
width) over the mix's saturated backlog, on a model of the same family cut
to ``reduced()`` size: admission, page growth and preemption depend on the
lengths and the pages alone, so the tiny model keeps the engine's page
accounting as the chip runs it. For each slot count and seed it prints the
preemptions and the most pages in use. Counts only; nothing is timed.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def replay(spec: dict, traffic: dict, slots: int, seed: int,
           blocks: int) -> dict:
    import dataclasses

    import jax

    from chipbench import bench, generator
    from repro.models import get_model
    from repro.runtime import Engine, Request

    spec = dict(spec, serving=dict(spec["serving"], num_slots=slots))
    cfg = bench.model_config(spec)
    ecfg = bench.engine_config(cfg, spec, traffic, seed)
    tiny = cfg.reduced()
    params = get_model(tiny).init_params(tiny, jax.random.PRNGKey(seed))
    lens = generator.lengths(traffic, slots, blocks)
    prompts = generator.prompts(lens, tiny.vocab_size, seed)
    reqs = [Request(rid=i, prompt=p, max_new_tokens=o)
            for i, (p, (_, o)) in enumerate(zip(prompts, lens))]
    rep = Engine(tiny, params, dataclasses.replace(ecfg)).run(reqs)
    return {"slots": slots, "seed": seed, "num_pages": ecfg.num_pages,
            "requests": len(reqs), "preemptions": rep.preemptions,
            "peak_live_pages": rep.peak_live_pages,
            "decode_steps": rep.decode_steps,
            "mean_live_slots": rep.useful_slot_steps
            / max(rep.decode_steps, 1)}


def main(argv=None) -> int:
    from chipbench import bench

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("config")
    ap.add_argument("traffic")
    ap.add_argument("--slots", required=True)
    ap.add_argument("--seeds", default="1,2,3")
    ap.add_argument("--blocks", type=int, default=8)
    args = ap.parse_args(argv)
    spec = bench.load_config(ROOT, args.config)
    traffic = bench.load_traffic(ROOT, args.traffic)
    for slots in (int(s) for s in args.slots.split(",")):
        for seed in (int(s) for s in args.seeds.split(",")):
            print(json.dumps(replay(spec, traffic, slots, seed,
                                    args.blocks)), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
