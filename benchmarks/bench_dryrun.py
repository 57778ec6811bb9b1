"""Summarize the multi-pod dry-run artifacts (launch.dryrun output).

Reads benchmarks/artifacts/dryrun/*.json. If the artifacts are missing,
runs the full sweep (64 cells x {16x16, 2x16x16}) in a subprocess — the
512 fake devices must be pinned before jax initializes.
"""

from __future__ import annotations

import glob
import json
import os
import subprocess
import sys

ART = "benchmarks/artifacts/dryrun"


def _ensure():
    if len(glob.glob(os.path.join(ART, "*.json"))) >= 64:
        return
    # CPU only: the parent may already hold the chip
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    subprocess.run([sys.executable, "-m", "repro.launch.dryrun",
                    "--arch", "all", "--shape", "all", "--mesh", "both"],
                   env=env, check=True, timeout=7200)


def run() -> list[dict]:
    _ensure()
    rows = []
    for path in sorted(glob.glob(os.path.join(ART, "*.json"))):
        with open(path) as f:
            rec = json.load(f)
        mem = rec.get("memory_analysis", {})
        coll = rec.get("collective_bytes_per_chip", {})
        rows.append({
            "name": f"dryrun/{rec['arch']}/{rec['shape']}/{rec['mesh']}",
            "ok": rec["ok"],
            "compile_s": rec.get("compile_s"),
            "arg_GiB": round(mem.get("argument_size_in_bytes", 0) / 2**30,
                             2),
            "temp_GiB": round(mem.get("temp_size_in_bytes", 0) / 2**30, 2),
            "flops_per_chip_raw": rec.get("cost_analysis", {}).get("flops"),
            "collective_MiB": round(sum(coll.values()) / 2**20, 1),
        })
    return rows


def check(rows):
    assert len(rows) == 64, f"expected 64 dry-run cells, got {len(rows)}"
    bad = [r["name"] for r in rows if not r["ok"]]
    assert not bad, f"dry-run failures: {bad}"
