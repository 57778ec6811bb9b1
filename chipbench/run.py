"""Run one benchmark cell once on the chip and print its result.

    python3 chipbench/run.py --workload rwkv6-1.6b.chat --seed 1 --seconds 10 \\
        --trace 0

From the root of a checkout, on a machine whose JAX finds the chips the
cell asks for. With ``--trace 0`` the result line holds the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics, read from a
profiler trace of the window. The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
with ``--trace 1`` ``breakdown``, and last ``checks``: each number compared
for ``correct`` beside its limit, which also end standard error. Any
failure, and a platform other than TPU, exits non-zero with no result.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        log(f"run: no program under {ROOT / 'src'}; nothing was run")
        return 2
    for p in (ROOT / "src", ROOT):
        sys.path.insert(0, str(p))

    import jax

    from chipbench import bench, peaks

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    cell = bench.resolve_cell(ROOT, args.workload)
    if device["platform"] != "tpu" or device["count"] < cell.chips:
        log(f"run: {args.workload} needs {cell.chips} TPU chip(s); JAX found "
            f"{device['count']} {device['platform']!r} device(s); nothing was"
            f" run")
        return 2
    out = bench.run_cell(ROOT, args.workload, args.seed, args.seconds,
                         bool(args.trace), t_start=T_START, device=device,
                         pk=peaks.peaks(device["kind"]), log=log)
    for name, c in out["checks"].items():
        log(f"{name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
