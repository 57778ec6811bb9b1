"""End-to-end training driver.

Wires the full stack: config -> model -> sharded step (pjit) -> data
pipeline -> AdamW -> checkpoint manager -> fault-tolerant supervisor.
It runs on a 1x1 mesh (one chip, or the CPU at ``reduced()`` size)
end-to-end; on a pod the same code takes ``--mesh pod`` (the dry-run
proves those cells compile).

  PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --steps 200 \
      --seq-len 128 --batch 8 --ckpt-dir /tmp/ckpt
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint import CheckpointManager
from ..configs import get_config
from ..data import TokenStream
from ..models import get_model, layers as L
from ..optim import adamw_init
from ..runtime import ElasticConfig, TrainingSupervisor
from . import sharding as sh
from .compile_cache import use_compile_cache
from .mesh import dp_axes, make_host_mesh, make_production_mesh
from .steps import make_train_step


def build(arch: str, *, reduced: bool, mesh, seq_len: int, batch: int,
          lr: float, steps: int, microbatches: int, seed: int = 0):
    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    api = get_model(cfg)
    key = jax.random.PRNGKey(seed)

    params = api.init_params(cfg, key)
    opt = adamw_init(params)
    p_spec = sh.param_pspecs(params, mesh)
    o_spec = sh.opt_pspecs(p_spec, mesh)
    params = jax.device_put(params, sh.to_shardings(p_spec, mesh))
    opt = jax.device_put(opt, sh.to_shardings(o_spec, mesh))

    stream = TokenStream(vocab_size=cfg.vocab_size, seq_len=seq_len,
                         global_batch=batch, seed=seed)
    step_fn = make_train_step(cfg, lr=lr, warmup=max(steps // 20, 5),
                              total=steps, microbatches=microbatches)
    b_spec = sh.batch_pspecs(
        {"tokens": jax.ShapeDtypeStruct((batch, seq_len), jnp.int32),
         "labels": jax.ShapeDtypeStruct((batch, seq_len), jnp.int32)},
        mesh)
    jitted = jax.jit(step_fn,
                     in_shardings=(sh.to_shardings(p_spec, mesh),
                                   sh.to_shardings(o_spec, mesh),
                                   sh.to_shardings(b_spec, mesh)),
                     donate_argnums=(0, 1))
    return cfg, params, opt, stream, jitted


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--full", action="store_true",
                    help="full config (pod mesh) instead of reduced")
    ap.add_argument("--mesh", default="host",
                    choices=("host", "pod", "multipod"))
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    use_compile_cache()
    mesh = {"host": make_host_mesh,
            "pod": make_production_mesh,
            "multipod": lambda: make_production_mesh(multi_pod=True)}[
        args.mesh]()

    dp = dp_axes(mesh)
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    token = L.set_shard_ctx(dp if len(dp) > 1 else (dp[0] if dp else None),
                            "model", dp_size)
    try:
        with mesh:
            cfg, params, opt, stream, jitted = build(
                args.arch, reduced=not args.full, mesh=mesh,
                seq_len=args.seq_len, batch=args.batch, lr=args.lr,
                steps=args.steps, microbatches=args.microbatches)

            mgr = CheckpointManager(args.ckpt_dir, keep=3)
            sup = TrainingSupervisor(
                mgr, ElasticConfig(checkpoint_every=args.ckpt_every))

            start = 0
            if mgr.latest_step() is not None:
                (params, opt), start = mgr.restore((params, opt))
                print(f"resumed from step {start}")

            losses = []
            t0 = time.monotonic()

            def step_fn(state, batch):
                p, o = state
                p, o, metrics = jitted(p, o, batch)
                losses.append(float(metrics["loss"]))
                n = len(losses)
                if n % args.log_every == 0:
                    dt = (time.monotonic() - t0) / n
                    print(f"step {start + n:5d} loss "
                          f"{np.mean(losses[-args.log_every:]):.4f} "
                          f"({dt * 1e3:.0f} ms/step)", flush=True)
                return (p, o), metrics

            (params, opt), report = sup.run(
                (params, opt), step_fn, stream.batch,
                start_step=start, num_steps=args.steps)

            print(f"done: {report.steps_done} steps, "
                  f"{report.retries} retries, {report.restores} restores; "
                  f"final loss {losses[-1]:.4f} "
                  f"(first {losses[0]:.4f})")
            return 0 if losses[-1] < losses[0] else 1
    finally:
        L.reset_shard_ctx(token)


if __name__ == "__main__":
    raise SystemExit(main())
