"""DeepSeek-V2 as the program serves it, against the plain reference
(``chipbench/reference/deepseek_v2.py``) on seeded weights at ``reduced()``
size: the leading dense layer, MLA's latent pages, YaRN, the expert share
and dropless routing; and the latent path's counters."""

import dataclasses
import json
import math
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import MoEConfig, ModelConfig, RopeScaling, get_config
from repro.models import get_model, layers as L, moe as MoE
from repro.runtime import Engine, EngineConfig, Request, tracing
from repro.runtime.kv_pager import PagerConfig

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench.reference import deepseek_v2 as R  # noqa: E402

PUBLISHED = RopeScaling(factor=40, original_max_position_embeddings=4096,
                        beta_fast=32, beta_slow=1, mscale=0.707,
                        mscale_all_dim=0.707)
SEED = 3


def _setup(seed=SEED):
    cfg = get_config("deepseek-v2-lite-16b").reduced()
    return cfg, MoE.init_params(cfg, jax.random.PRNGKey(seed))


def _ref_logits(cfg, seed, tokens, positions):
    """The reference's whole logit rows (B, P, V) at ``positions``: every
    vocabulary id is a probe."""
    B, P = positions.shape
    probes = np.broadcast_to(np.arange(cfg.vocab_size, dtype=np.int32)
                             [None, :, None], (B, cfg.vocab_size, P))
    _, at, _ = R.score(dataclasses.asdict(cfg), seed, tokens, positions,
                       probes)
    return np.asarray(at).transpose(0, 2, 1)


# The reference is float32 at highest precision. The program in float32
# differs from it only by the order of its sums and its absorbed MLA
# (W_uk folded into the query), so 1e-4 is float32 rounding over two
# layers; in bfloat16 (the served precision) each projection rounds its
# operands to 8 bits of mantissa, which at width 64 moves logits of O(1)
# by up to a few hundredths, so 0.1 leaves room and still fails a token
# one id off or a stale cache, which move them by O(1).
TOL = {"float32": 1e-4, "bfloat16": 0.1}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_program_matches_reference(monkeypatch, dtype):
    """Prefill logits, then decode through the latent pages: per-step
    logits teacher-forced, and the fused horizon's greedy tokens, whose
    reference logit may lie below the reference's best by the tolerance
    at most. Two slots with prompts of 13 and 7 tokens (padded to a
    16-token bucket) share one step."""
    monkeypatch.setattr(L, "COMPUTE_DTYPE", getattr(jnp, dtype))
    cfg, params = _setup()
    page, n_dec = 4, 6
    lens = [13, 7]
    seqs = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                                         cfg.vocab_size), np.int32)
    state = MoE.init_paged_decode_state(cfg, 17, page,
                                        dtype=L.COMPUTE_DTYPE)
    table = np.zeros((2, 8), np.int32)
    table[0], table[1] = np.arange(1, 9), np.arange(9, 17)
    tol = TOL[dtype]
    for b, n in enumerate(lens):
        toks = np.zeros((1, 16), np.int32)
        toks[0, :n] = seqs[b, :n]
        last, lat = MoE.paged_prefill(cfg, params, {"tokens": toks},
                                      jnp.asarray([n]))
        state = MoE.write_prefill_pages(cfg, state, lat[:, 0],
                                        jnp.asarray(table[b, :4]))
        want = _ref_logits(cfg, SEED, seqs[b:b + 1, :n], np.asarray([[n - 1]]))
        np.testing.assert_allclose(np.asarray(last)[0], want[0, 0],
                                   rtol=0, atol=tol)
    fused = state
    lengths = np.asarray(lens, np.int32)
    active = np.ones(2, bool)
    steps = []
    for i in range(n_dec):
        tok = np.asarray([seqs[b, lens[b] + i] for b in range(2)], np.int32)
        logits, state = MoE.paged_decode_step(
            cfg, params, state, jnp.asarray(tok), jnp.asarray(table),
            jnp.asarray(lengths + i), jnp.asarray(active))
        steps.append(np.asarray(logits))
    for b, n in enumerate(lens):
        pos = np.arange(n, n + n_dec)[None]
        want = _ref_logits(cfg, SEED, seqs[b:b + 1, :n + n_dec], pos)[0]
        np.testing.assert_allclose(np.stack(steps)[:, b], want, rtol=0,
                                   atol=tol)
    # the fused horizon, greedy from each prompt's own last token
    out, _, _, _, _ = MoE.paged_decode_multi(
        cfg, params, fused, jnp.asarray([seqs[0, 12], seqs[1, 6]]),
        jnp.asarray(lengths - 1), jnp.full((2,), n_dec), jnp.asarray(table),
        jnp.asarray(active), jnp.asarray(n_dec), hmax=8)
    out = np.asarray(out)[:n_dec]
    for b, n in enumerate(lens):
        seq = np.concatenate([seqs[b, :n], out[:, b]])[None]
        pos = np.arange(n - 1, n - 1 + n_dec)[None]
        best, at, _ = R.score(dataclasses.asdict(cfg), SEED, seq, pos,
                              out[None, None, :, b])
        gap = np.asarray(best)[0] - np.asarray(at)[0, 0]
        assert gap.max() <= tol, gap


def test_yarn_at_published_widths():
    """DeepSeek-V2-Lite's rope head of 64 at base 1e4 over an original
    4096: YaRN ramps pairs 10..23, interpolates by 40 above, keeps theta
    below, scales cos and sin by 1, and the softmax scale by
    (0.1 * 0.707 * ln 40 + 1)^2 = 1.5896."""
    assert L.yarn_range(PUBLISHED, 64, 1e4) == (10, 23)
    assert L.yarn_mscale(40, 0.707) ** 2 == pytest.approx(1.5896, abs=1e-4)
    i = np.arange(32, dtype=np.float64)
    freq = 1e4 ** (-i / 32)
    keep = 1 - np.clip((i - 10) / 13, 0, 1)
    want = freq / 40 * (1 - keep) + freq * keep
    got = np.asarray(L.rope_freqs(64, 1e4, PUBLISHED))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert got[:10] == pytest.approx(freq[:10], rel=1e-6)
    assert got[23:] == pytest.approx(freq[23:] / 40, rel=1e-6)
    ref, cos_scale = R.inv_freq({"rope_theta": 10000.0, **{
        f"rope_scaling.{k}": v
        for k, v in dataclasses.asdict(PUBLISHED).items()}}, 64)
    np.testing.assert_allclose(ref, want, rtol=1e-12)
    assert cos_scale == 1.0
    full = get_config("deepseek-v2-lite-16b")
    assert MoE.softmax_scale(full) == pytest.approx(
        192 ** -0.5 * 1.5896, rel=1e-4)
    # no scaling: plain RoPE and 1/sqrt(192), as before
    plain = dataclasses.replace(full, rope_scaling=None)
    assert MoE.softmax_scale(plain) == pytest.approx(192 ** -0.5, rel=1e-12)
    np.testing.assert_array_equal(np.asarray(L.rope_freqs(64, 1e4)),
                                  np.asarray(L.rope_freqs(64, 1e4, None)))


def _moe_params(key, E, D=32, F=16):
    ks = jax.random.split(key, 5)
    return {"router": jax.random.normal(ks[0], (D, E)) * 0.5,
            "w_gate": jax.random.normal(ks[1], (E, D, F)) * 0.2,
            "w_up": jax.random.normal(ks[2], (E, D, F)) * 0.2,
            "w_down": jax.random.normal(ks[3], (E, F, D)) * 0.2}


def test_expert_shares_add_up_to_the_uncut_layer():
    """Four chips each hold two of eight routed experts (ids 2s, 2s+1) and
    route over all eight: their parts plus the shared expert, counted
    once, equal the uncut layer. Each part is nonzero, and a part leaves
    out the claims of the experts held elsewhere."""
    E, k, N = 8, 3, 40
    p = _moe_params(jax.random.PRNGKey(0), E)
    x = jax.random.normal(jax.random.PRNGKey(1), (N, 32))
    shared = L.swiglu(x, *(jax.random.normal(jax.random.PRNGKey(2 + i), s)
                           * 0.2 for i, s in
                           enumerate(((32, 16), (32, 16), (16, 32)))))
    whole, _ = L.moe_ffn(x, p, L.MoEDims(E, k, N))
    parts = []
    for s in range(4):
        share = {n: (v[2 * s:2 * s + 2] if n != "router" else v)
                 for n, v in p.items()}
        y, _, keep = L.moe_ffn(x, share, L.MoEDims(2, k, N, first=2 * s),
                               return_keep=True)
        parts.append(y)
        assert 0 < int(keep.sum()) < N * k
        dense, _ = L.moe_ffn_dense(x, share, L.MoEDims(2, k, N, first=2 * s))
        np.testing.assert_allclose(np.asarray(y), np.asarray(dense),
                                   rtol=1e-5, atol=1e-5)
    # float32 sums in another order: 1e-5
    np.testing.assert_allclose(np.asarray(sum(parts) + shared),
                               np.asarray(whole + shared), rtol=1e-5,
                               atol=1e-5)


def test_no_claim_is_dropped_when_every_token_routes_to_one_expert():
    """A router that sends every token's first claim to expert 0: dropless
    dims keep all N of them (the expert's rows are the token-by-token
    SwiGLU, weighted by the gate), where the training capacity ceiling
    drops most."""
    cfg = get_config("deepseek-v2-lite-16b").reduced()
    E, N, D = cfg.moe.num_experts, 64, 32
    p = _moe_params(jax.random.PRNGKey(3), cfg.moe.router_width, D)
    p = {n: (v[:E] if n != "router" else v) for n, v in p.items()}
    p["router"] = p["router"].at[:, 0].set(0.0).at[0, 0].set(50.0)
    x = jax.random.normal(jax.random.PRNGKey(4), (N, D)).at[:, 0].set(1.0)
    dims = L.moe_dims_dropless(cfg, N)
    y, _, keep = L.moe_ffn(x, p, dims, return_keep=True)
    probs = jax.nn.softmax(x @ p["router"], axis=-1)
    vals, idx = jax.lax.top_k(probs, cfg.moe.top_k)
    assert (np.asarray(idx[:, 0]) == 0).all()
    held = np.asarray(idx) < E
    np.testing.assert_array_equal(np.asarray(keep), held)
    want = sum(jnp.where(idx[:, j:j + 1] == e, vals[:, j:j + 1], 0.0)
               * L.swiglu(x, p["w_gate"][e], p["w_up"][e], p["w_down"][e])
               for e in range(E) for j in range(cfg.moe.top_k))
    # float32, one token at a time against grouped rows: 1e-5
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-5,
                               atol=1e-5)
    capped, _, kept = L.moe_ffn(x, p, L.moe_dims(cfg, N), return_keep=True)
    assert int(kept[:, 0].sum()) < N
    assert not np.allclose(np.asarray(capped), np.asarray(y))


def test_config_builds_from_json_mappings():
    """The benchmark's configuration file builds ModelConfig unchanged:
    nested groups arrive as mappings. The latent page holds one row of
    kv_lora_rank + rope per token and layer, and the backend uses it."""
    spec = json.loads((ROOT / "chipbench/configs/deepseek-v2-lite.json")
                      .read_text())
    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    cfg = ModelConfig(name="x", **{k: v for k, v in spec["model"].items()
                                   if k in fields})
    assert isinstance(cfg.moe, MoEConfig) and cfg.moe.router_width == 64
    assert cfg.rope_scaling == PUBLISHED and cfg.first_k_dense == 1
    hash(cfg)                                   # a static jit argument
    assert PagerConfig(2, 16, 129).page_bytes(cfg) == 9 * 16 * 576 * 2
    full = get_config("deepseek-v2-lite-16b")
    assert (full.d_ff, full.first_k_dense, full.moe.router_width) \
        == (10944, 1, 64)
    shapes = jax.eval_shape(lambda: MoE.init_params(
        cfg, jax.random.PRNGKey(0)))
    n = sum(math.prod(a.shape) for a in jax.tree.leaves(shapes))
    assert n == 1_857_333_760
    assert shapes["dense"]["mlp"]["w_gate"].shape == (1, 2048, 10944)
    assert shapes["blocks"]["moe"]["router"].shape == (8, 2048, 64)
    assert shapes["blocks"]["moe"]["w_gate"].shape == (8, 16, 2048, 1408)
    small, params = _setup()
    ecfg = EngineConfig(num_slots=2, page_size=8, num_pages=9,
                        max_pages_per_seq=8, prefill_bucket=8)
    eng = Engine(small, params, ecfg)
    assert eng.backend.page_bytes == ecfg.pager.page_bytes(small) \
        == 2 * 8 * (32 + 8) * 2


@pytest.mark.parametrize("model, error", [
    ({"rope_scaling": {"type": "linear", "factor": 4,
                       "original_max_position_embeddings": 4096}},
     "rope scaling 'linear'"),
    ({"first_k_dense": 3}, "first_k_dense 3 of 2 layers"),
])
def test_config_refuses_what_the_program_cannot_run(model, error):
    """A configuration file asking for a rope scaling other than YaRN, or
    more leading dense layers than layers, is refused with the reason."""
    with pytest.raises(ValueError, match=error):
        ModelConfig(name="x", family="moe", num_layers=2, d_model=64,
                    num_heads=4, num_kv_heads=4, d_ff=128, vocab_size=256,
                    **model)


@pytest.mark.parametrize("held", ["all", "half"])
def test_latent_counters_count_claims_and_reads(held):
    """One sample per decode dispatch in each ring. With every expert held
    the live claims are exactly live slot-steps x top_k x MoE layers;
    with half held, at most that. Rows computed are steps x MoE layers x
    held experts x capacity; live latent rows are each request's context
    plus its new token at every decode step, gathered rows every slot's
    whole table at every step."""
    cfg, params = _setup()
    if held == "all":
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, num_experts=8, router_experts=0))
        params = MoE.init_params(cfg, jax.random.PRNGKey(SEED))
    ecfg = EngineConfig(num_slots=3, page_size=8, num_pages=40,
                        max_pages_per_seq=6, prefill_bucket=8, horizon=4)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(0, 256, n).astype(np.int32),
                    max_new_tokens=g)
            for i, (n, g) in enumerate([(5, 9), (12, 4), (9, 13), (3, 6)])]
    t0 = time.monotonic()
    rep = Engine(cfg, params, ecfg).run(reqs)
    routes, reads, disp = (getattr(tracing, r).window(t0, math.inf)
                           for r in ("ROUTES", "LATENT_READS", "DISPATCHES"))
    assert rep.preemptions == 0 and len(routes) == len(reads) == len(disp)
    n_moe = cfg.num_layers - cfg.first_k_dense
    rows = sum(s.h for s in disp) * n_moe * cfg.moe.num_experts * 4
    assert sum(s.rows for s in routes) == rows
    slot_steps = rep.useful_slot_steps
    claims = sum(s.claims for s in routes)
    if held == "all":
        assert claims == slot_steps * cfg.moe.top_k * n_moe
    else:
        assert 0 < claims < slot_steps * cfg.moe.top_k * n_moe
    live = sum(len(r.prompt) + j - 1 for r in rep.completed
               for j in range(2, len(r.generated) + 1))
    assert sum(s.live for s in reads) == live
    assert sum(s.gathered for s in reads) \
        == sum(s.h for s in disp) * 3 * 6 * 8


def test_engines_of_one_config_share_their_programs():
    """A second engine of the same config (a reload, the next run in one
    process) reuses the first's compiled prefill and decode programs and
    serves the same tokens; another horizon gets programs of its own."""
    cfg, params = _setup()
    ecfg = EngineConfig(num_slots=2, page_size=8, num_pages=20,
                        max_pages_per_seq=6, prefill_bucket=8, horizon=4)
    prompt = np.arange(1, 12, dtype=np.int32)

    def serve(e):
        eng = Engine(cfg, params, e)
        rep = eng.run([Request(rid=0, prompt=prompt, max_new_tokens=6)])
        return eng.backend, list(rep.completed[0].generated)

    first, toks = serve(ecfg)
    sizes = (first._prefill._cache_size(),
             first._decode_multi._cache_size())
    second, again = serve(ecfg)
    assert second._prefill is first._prefill and again == toks
    assert (second._prefill._cache_size(),
            second._decode_multi._cache_size()) == sizes
    other, _ = serve(dataclasses.replace(ecfg, horizon=2))
    assert other._decode_multi is not first._decode_multi
