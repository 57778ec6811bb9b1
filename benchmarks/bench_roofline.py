"""Roofline terms per (arch x shape) cell on the single-pod mesh.

Per cell, from the compiled dry-run artifact (per-device SPMD module):

  compute term    = HLO_FLOPs / peak_FLOPs          (197 bf16 TFLOP/s)
  memory term     = HLO_bytes / HBM_bw              (819 GB/s)
  collective term = collective_bytes / link_bw      (~50 GB/s/link)

HLO_FLOPs / bytes are TRIP-COUNT-CORRECTED via hlo_analysis (XLA's
cost_analysis counts while bodies once — see that module's docstring;
both raw and corrected values are recorded). MODEL_FLOPS = 6·N_active·T
(train) or 2·N_active·T (prefill/decode), per chip; the ratio
MODEL/HLO exposes remat + MoE-capacity + attention overheads.

  PYTHONPATH=src:. python -m benchmarks.bench_roofline [--arch ...]
"""

from __future__ import annotations

import argparse
import json
import os
import time

PEAK_FLOPS = 197e12          # bf16 / chip
HBM_BW = 819e9               # bytes/s / chip
LINK_BW = 50e9               # bytes/s / ICI link
CHIPS = 256                  # single pod (16 x 16)


def model_flops_per_chip(cfg, shape) -> float:
    n = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        factor = 6.0
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        factor = 2.0
    else:                      # decode: one token per sequence
        tokens = shape.global_batch
        factor = 2.0
    return factor * n * tokens / CHIPS


def advise(dom: str, kind: str) -> str:
    return {
        "compute": "compute-bound: raise MXU utilization (larger "
                   "microbatch per chip, fuse small matmuls via the "
                   "packed canvas, drop remat where memory allows)",
        "memory": "memory-bound: cut HBM traffic (weight-stationary "
                  "reuse, bf16/int8 compute copies, larger per-chip "
                  "batch amortizing weight reads)"
        + (", paged/quantized KV cache" if kind == "decode" else ""),
        "collective": "collective-bound: reshard to cut gathers "
                      "(wide-TP for weights, head-aligned KV, "
                      "overlap via latency-hiding scheduler)",
    }[dom]


def run_cell(arch: str, shape_name: str) -> dict:
    import jax
    from benchmarks.hlo_analysis import executed_totals
    from repro.configs import SHAPES, get_config
    from repro.launch.mesh import make_production_mesh
    from repro.launch.steps import abstract_cell, lower_cell

    mesh = make_production_mesh()
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    cell = abstract_cell(cfg, shape_name, mesh)
    t0 = time.monotonic()
    compiled = lower_cell(cell, mesh).compile()
    compile_s = time.monotonic() - t0

    tot = executed_totals(compiled.as_text())
    raw = compiled.cost_analysis() or {}
    mem = compiled.memory_analysis()

    t_c = tot["flops"] / PEAK_FLOPS
    t_m = tot["touched_bytes"] / HBM_BW
    t_x = tot["collective_bytes_total"] / LINK_BW
    terms = {"compute": t_c, "memory": t_m, "collective": t_x}
    dom = max(terms, key=terms.get)
    mf = model_flops_per_chip(cfg, shape)
    bound = max(terms.values())

    return {
        "arch": arch, "shape": shape_name, "kind": cell.kind,
        "mesh": "16x16", "compile_s": round(compile_s, 2),
        "hlo_flops_per_chip": tot["flops"],
        "hlo_bytes_per_chip": tot["touched_bytes"],
        "collective_bytes_per_chip": tot["collective_bytes"],
        "collective_total_per_chip": tot["collective_bytes_total"],
        "raw_cost_analysis_flops": float(raw.get("flops", 0.0)),
        "raw_bytes_accessed": float(raw.get("bytes accessed", 0.0)),
        "temp_bytes_per_chip": int(getattr(mem, "temp_size_in_bytes", 0)),
        "arg_bytes_per_chip": int(getattr(mem, "argument_size_in_bytes",
                                          0)),
        "terms_s": {k: round(v, 6) for k, v in terms.items()},
        "dominant": dom,
        "step_lower_bound_s": round(bound, 6),
        "model_flops_per_chip": mf,
        "model_over_hlo_flops": round(mf / tot["flops"], 4)
        if tot["flops"] else None,
        "useful_roofline_fraction": round(
            (mf / PEAK_FLOPS) / bound, 8) if bound else None,
        "advice": advise(dom, cell.kind),
    }


ART = "benchmarks/artifacts/roofline"


def sweep(archs=None, out_dir=ART):
    from repro.configs import ARCH_IDS, shapes_for
    os.makedirs(out_dir, exist_ok=True)
    rows = []
    for arch in archs or ARCH_IDS:
        for shape_name in shapes_for(arch):
            cid = f"{arch}__{shape_name}"
            print(f"=== {cid}", flush=True)
            rec = run_cell(arch, shape_name)
            rows.append(rec)
            with open(os.path.join(out_dir, cid + ".json"), "w") as f:
                json.dump(rec, f, indent=1)
            t = rec["terms_s"]
            print(f"    compute {t['compute'] * 1e3:9.2f} ms | "
                  f"memory {t['memory'] * 1e3:9.2f} ms | "
                  f"collective {t['collective'] * 1e3:9.2f} ms "
                  f"-> {rec['dominant']}; useful-roofline "
                  f"{rec['useful_roofline_fraction']}", flush=True)
    return rows


def run() -> list[dict]:
    """benchmarks.run entry: executes the sweep in a SUBPROCESS (the 512
    fake devices must be pinned before jax init, and sibling benches have
    already initialized jax in this process), then reads the artifacts.
    The child is held to the CPU: a parent that has touched JAX holds the
    chip, so a child that opened the TPU would fail or hang."""
    import glob
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=512",
               PYTHONPATH="src:.")
    subprocess.run([sys.executable, "-m", "benchmarks.bench_roofline"],
                   env=env, check=True, timeout=7200)
    rows = []
    for path in sorted(glob.glob(os.path.join(ART, "*.json"))):
        with open(path) as f:
            rec = json.load(f)
        rows.append({"name": f"roofline/{rec['arch']}/{rec['shape']}",
                     "dominant": rec["dominant"],
                     "useful_roofline_fraction":
                         rec["useful_roofline_fraction"],
                     "terms_ms": {k: round(v * 1e3, 2)
                                  for k, v in rec["terms_s"].items()}})
    return rows


def check(rows):
    assert len(rows) >= 32, f"expected >=32 roofline cells, got {len(rows)}"
    for r in rows:
        f = r["useful_roofline_fraction"]
        assert f is None or 0 <= f <= 1.0, (r["name"], f)


def main(argv=None):
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=512")
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    args = ap.parse_args(argv)
    archs = None if args.arch == "all" else args.arch.split(",")
    sweep(archs)
    return 0


if __name__ == "__main__":
    import sys
    sys.path.insert(0, "src")
    raise SystemExit(main())
