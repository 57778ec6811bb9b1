"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

This proves the distribution config is coherent without hardware: 512
placeholder host devices stand in for 2 x (16 x 16) TPU v5e pods; the
SPMD partitioner runs for real, so sharding mismatches, non-divisible
dims, OOM-at-compile and unsupported collectives all fail HERE.

Per cell it records (benchmarks/artifacts/dryrun/<cell>.json):
  * memory_analysis(): per-device argument/output/temp/peak bytes,
  * cost_analysis(): FLOPs / bytes accessed (per-partition),
  * the collective mix parsed from the partitioned HLO (bytes per chip
    for all-gather / all-reduce / reduce-scatter / all-to-all /
    collective-permute) — the roofline's collective term.

The entry point pins the CPU backend with 512 host devices before JAX
starts a backend; importing this module changes no flag.

Usage:
  python -m repro.launch.dryrun --arch all --shape all --mesh both
  python -m repro.launch.dryrun --arch whisper-tiny --shape train_4k \
      --mesh single
"""

import argparse
import json
import os
import re
import time
import traceback

import jax

from ..configs import ARCH_IDS, get_config, shapes_for
from .mesh import make_production_mesh
from .steps import abstract_cell, lower_cell

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
                "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8, "c64": 8, "c128": 16}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(text: str) -> int:
    """Total bytes of all typed shapes in an HLO result-type string."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Per-chip result bytes of each collective kind in a partitioned
    module (the module is per-device, so shapes are already per-chip).

    Convention: we count the RESULT shape of each op — what lands on the
    chip (all-gather: the gathered tensor; reduce-scatter: the scattered
    shard; all-to-all / permute: the exchanged buffer).
    """
    out = {k: 0 for k in COLLECTIVES}
    for line in hlo_text.splitlines():
        s = line.strip()
        m = re.match(r"(?:ROOT\s+)?%?[\w\.\-]+\s*=\s*(.*?)\s*"
                     r"(all-gather|all-reduce|reduce-scatter|all-to-all|"
                     r"collective-permute)(-start)?\(", s)
        if m:
            out[m.group(2)] += _shape_bytes(m.group(1))
    return out


def run_cell(arch: str, shape_name: str, multi_pod: bool) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    cfg = get_config(arch)
    t0 = time.monotonic()
    cell = abstract_cell(cfg, shape_name, mesh)
    lowered = lower_cell(cell, mesh)
    t1 = time.monotonic()
    compiled = lowered.compile()
    t2 = time.monotonic()

    mem = compiled.memory_analysis()
    mem_d = {}
    if mem is not None:
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes",
                  "alias_size_in_bytes"):
            v = getattr(mem, k, None)
            if v is not None:
                mem_d[k] = int(v)
    cost = compiled.cost_analysis() or {}
    cost_d = {k: float(v) for k, v in cost.items()
              if isinstance(v, (int, float)) and (
                  "flops" in k or "bytes" in k or "utilization" in k.lower()
              )}
    coll = collective_bytes(compiled.as_text())

    print(compiled.memory_analysis())
    print({k: cost_d.get(k) for k in ("flops", "bytes accessed")})

    return {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "kind": cell.kind,
        "lower_s": round(t1 - t0, 2), "compile_s": round(t2 - t1, 2),
        "memory_analysis": mem_d,
        "cost_analysis": cost_d,
        "collective_bytes_per_chip": coll,
        "ok": True,
    }


def main(argv=None):
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    jax.config.update("jax_platforms", "cpu")
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both",
                    choices=("single", "multi", "both"))
    ap.add_argument("--out", default="benchmarks/artifacts/dryrun")
    ap.add_argument("--fail-fast", action="store_true")
    args = ap.parse_args(argv)

    archs = list(ARCH_IDS) if args.arch == "all" else args.arch.split(",")
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    os.makedirs(args.out, exist_ok=True)

    failures = []
    for arch in archs:
        shapes = shapes_for(arch) if args.shape == "all" \
            else args.shape.split(",")
        for shape_name in shapes:
            for multi in meshes:
                cell_id = (f"{arch}__{shape_name}__"
                           f"{'multi' if multi else 'single'}")
                path = os.path.join(args.out, cell_id + ".json")
                print(f"=== {cell_id}", flush=True)
                try:
                    rec = run_cell(arch, shape_name, multi)
                except Exception as e:  # noqa: BLE001
                    traceback.print_exc()
                    rec = {"arch": arch, "shape": shape_name,
                           "mesh": "multi" if multi else "single",
                           "ok": False, "error": f"{type(e).__name__}: {e}"}
                    failures.append(cell_id)
                    if args.fail_fast:
                        with open(path, "w") as f:
                            json.dump(rec, f, indent=1)
                        raise
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
                print(f"    -> {'OK' if rec['ok'] else 'FAIL'} "
                      f"(lower {rec.get('lower_s', '-')}s, "
                      f"compile {rec.get('compile_s', '-')}s)", flush=True)

    print(f"\n{len(failures)} failures" + (": " + ", ".join(failures)
                                           if failures else ""))
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
