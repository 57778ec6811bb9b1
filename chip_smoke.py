"""Serve olmo-1b at its published size on one TPU chip, once, and check it.

    python chip_smoke.py

The quickest proof that the system still starts on the chip. In order:

1. Refuse any backend but TPU: on any other it exits non-zero before
   building a model.
2. Kernel: the paged decode-attention Pallas kernel (``impl="auto"``, which
   is the kernel on TPU) against its jnp reference at olmo-1b widths.
3. Serve: olmo-1b (arXiv:2402.00838) at its published config with seeded
   random weights, through ``runtime.Engine`` with greedy horizon-fused
   decode, over a seeded Poisson trace of 16 requests. Every request must
   complete untruncated with in-vocabulary tokens; the compiled fused
   decode step must hold the Pallas kernel (``tpu_custom_call``); and each
   served token must be the greedy choice of the reference forward pass
   (dense cache, jnp attention), whose logits must be finite.

Every figure printed on the way is bring-up output, not a measurement of
speed. The last line of stdout is ``{"ok": true, "device": {...}}``; any
failure raises and exits non-zero without it.
"""

from __future__ import annotations

import json
import sys
import time
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SEED = 0
ARCH = "olmo-1b"
SLOTS, PAGE = 8, 16
PROMPT_LENS, GEN_LENS = (64, 128, 256), (32, 64, 128)
N_REQUESTS = 16

# Kernel vs reference. Both accumulate in float32 and round the output to
# bfloat16 once, so rounding alone can part them by one bf16 step (2^-7 of
# the output's magnitude). The kernel's MXU passes may also round the
# scaled query and the probabilities to bf16 (2^-9 relative each, 2^-8
# together). 2^-6 of the largest output covers the sum twice over; a wrong
# page, length or mask moves outputs by O(1).
KERNEL_TOL = 2.0 ** -6

# Served token vs reference logits. The engine's decode (paged cache,
# Pallas attention, f32 softmax) and the reference forward (dense cache,
# jnp attention on bf16 scores) round differently in a bf16 residual
# stream, so near-ties may resolve either way: the served token's reference
# logit must be within this of the reference maximum. Random olmo-1b
# logits have a spread of about 0.9 (embedding std 0.02 x sqrt(2048)); a
# token chosen from wrong logits falls about 3 spreads below the maximum.
LOGIT_TOL = 0.25


def device_info() -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def check_kernel(cfg, key) -> float:
    """Largest |kernel - reference| of one paged decode-attention call at
    the widths of ``cfg``: 8 slots, pages of 16 rows, 32 table columns,
    random live lengths up to 512."""
    from repro.kernels import ops

    b, m = SLOTS, 32
    kv, dh, h = cfg.num_kv_heads, cfg.head_dim, cfg.num_heads
    n_pages = 1 + b * m                          # page 0 is the trash page
    kq, kk, kv_, kp, kl = jax.random.split(key, 5)
    q = jax.random.normal(kq, (b, h, dh), jnp.bfloat16)
    k_pages = jax.random.normal(kk, (kv, n_pages, PAGE, dh), jnp.bfloat16)
    v_pages = jax.random.normal(kv_, (kv, n_pages, PAGE, dh), jnp.bfloat16)
    table = (1 + jax.random.permutation(kp, b * m)).reshape(b, m)
    lengths = jax.random.randint(kl, (b,), 1, m * PAGE + 1)
    args = (q, k_pages, v_pages, table.astype(jnp.int32), lengths)
    got = ops.paged_decode_attention(*args)
    with jax.default_matmul_precision("highest"):
        want = ops.paged_decode_attention(*args, impl="ref")
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    err = float(np.abs(got - want).max())
    tol = KERNEL_TOL * float(np.abs(want).max())
    print(f"kernel: paged_decode_attention max_abs_err={err!r} tol={tol!r} "
          f"lengths={np.asarray(lengths).tolist()}", flush=True)
    assert np.isfinite(got).all(), "kernel output is not finite"
    assert err <= tol, f"kernel differs from reference: {err} > {tol}"
    return err


def serve(cfg, seed: int):
    """Serve the trace through the engine; returns (engine, report)."""
    from repro.launch.mesh import make_host_mesh
    from repro.launch.serve import init_sharded_params
    from repro.runtime import Engine, EngineConfig, poisson_trace

    max_len = max(PROMPT_LENS) + max(GEN_LENS)
    pages_per_seq = -(-max_len // PAGE) + 1
    ecfg = EngineConfig(
        num_slots=SLOTS, page_size=PAGE,
        num_pages=1 + pages_per_seq * SLOTS * 2,
        max_pages_per_seq=pages_per_seq, prefill_bucket=PAGE,
        greedy=True, seed=seed)
    trace = poisson_trace(
        N_REQUESTS, mean_interarrival=0.5, prompt_lens=PROMPT_LENS,
        gen_lens=GEN_LENS, vocab_size=cfg.vocab_size, seed=seed)
    mesh = make_host_mesh()
    with mesh:
        t0 = time.monotonic()
        params = jax.block_until_ready(init_sharded_params(cfg, mesh, seed))
        engine = Engine(cfg, params, ecfg)
        print(f"serve: {cfg.name} params={cfg.param_count()} "
              f"init_wall_s={time.monotonic() - t0!r}", flush=True)
        rep = engine.run(trace)
    print("serve: " + json.dumps(rep.summary()), flush=True)

    assert len(rep.completed) == N_REQUESTS, len(rep.completed)
    for r in rep.completed:
        toks = np.asarray(r.generated)
        assert not r.truncated, f"request {r.rid} truncated"
        assert len(toks) == r.max_new_tokens, (r.rid, len(toks))
        assert ((toks >= 0) & (toks < cfg.vocab_size)).all(), r.rid
    return engine, rep


def check_decode_step_has_kernel(engine) -> None:
    """The engine's compiled fused decode step must call the Pallas kernel
    (it would not if ops had fallen back to the jnp reference)."""
    be = engine.backend
    b, m = SLOTS, engine.ecfg.max_pages_per_seq
    vec = jnp.zeros((b,), jnp.int32)
    text = be._decode_multi.lower(
        be.params, be.state, vec, vec, vec, jnp.zeros((b, m), jnp.int32),
        jnp.zeros((b,), bool), jnp.asarray(1, jnp.int32), None,
    ).compile().as_text()
    assert "tpu_custom_call" in text, "decode step holds no Pallas kernel"
    print("decode step: tpu_custom_call present", flush=True)


def check_against_reference(cfg, params, completed) -> float:
    """Teacher-force each request's prompt + served tokens through the
    reference forward pass; return the largest gap between the reference
    maximum and the served token's reference logit."""
    from repro.models import get_model

    width = max(PROMPT_LENS) + max(GEN_LENS)
    forward = jax.jit(partial(get_model(cfg).forward, cfg))
    worst = 0.0
    for r in completed:
        seq = np.concatenate([r.prompt, r.generated]).astype(np.int32)
        toks = np.zeros((1, width), np.int32)
        toks[0, :len(seq)] = seq
        logits = np.asarray(forward(params, {"tokens": jnp.asarray(toks)}))
        plen, gen = len(r.prompt), np.asarray(r.generated)
        rows = logits[0, plen - 1:plen - 1 + len(gen)]   # predict gen[i]
        assert np.isfinite(rows).all(), f"request {r.rid}: non-finite logits"
        gap = rows.max(-1) - rows[np.arange(len(gen)), gen]
        worst = max(worst, float(gap.max()))
    print(f"reference: worst_logit_gap={worst!r} tol={LOGIT_TOL!r} "
          f"tokens={sum(len(r.generated) for r in completed)}", flush=True)
    assert worst <= LOGIT_TOL, f"served tokens leave the reference: {worst}"
    return worst


def main() -> int:
    dev = device_info()
    print("device: " + json.dumps(dev), flush=True)
    if dev["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev['platform']!r}; "
              "nothing was run", file=sys.stderr)
        return 1

    from repro.configs import get_config
    from repro.launch.compile_cache import use_compile_cache

    print(f"compile cache: {use_compile_cache()}", flush=True)
    cfg = get_config(ARCH)
    check_kernel(cfg, jax.random.PRNGKey(SEED))
    engine, rep = serve(cfg, SEED)
    stats = jax.devices()[0].memory_stats() or {}
    print(f"memory: peak_bytes_in_use={stats.get('peak_bytes_in_use')}",
          flush=True)
    check_decode_step_has_kernel(engine)
    check_against_reference(cfg, engine.backend.params, rep.completed)
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
