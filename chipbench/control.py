"""Readings that a cell's correctness limit is set from, on the chip.

    python3 chipbench/control.py --workload rwkv6-1.6b.chat --seconds 10 \\
        --seeds 11,12,13 [--control]

For each seed, in one process: the cell's own set-up and window at its own
load (``bench.serve``), then ``check.check`` on the window's served
requests. It prints one JSON line per seed with the program's verdict and
``max_logit_gap`` beside the limit and, with ``--control``, the verdict of
the same comparison for the float8 control put in the program's place (the
reference one precision step below bfloat16, reading the gap of the token
it ranks first at the same positions of the same prompts and served
tokens). The limit lies above the largest program reading over a dozen
seeds and below the smallest control reading; ``PERF.md`` gives both. The
benchmark's own runs never run this.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    for p in (ROOT / "src", ROOT):
        sys.path.insert(0, str(p))

    import jax

    from chipbench import bench, check, peaks

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"control: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    pk = peaks.peaks(dev.device_kind)
    cell = bench.resolve_cell(ROOT, args.workload)
    ref = bench.load_reference(cell.config)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.monotonic()
        run, mem = bench.serve(cell, seed, args.seconds, trace=False,
                               t_start=t0, pk=pk,
                               log=lambda m: print(m, file=sys.stderr))
        t1 = time.monotonic()
        v = check.check(run, ref, seed, control=args.control,
                        log=lambda m: print(m, file=sys.stderr))
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": v["correct"], "checks": v["checks"],
                          **({"control": v["control"]} if args.control
                             else {}),
                          "reference_s": time.monotonic() - t1,
                          "memory_peak_bytes": mem,
                          "setup_s": run.setup_s}), flush=True)
        del run
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
