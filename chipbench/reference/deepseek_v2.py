"""Plain float32 reference of DeepSeek-V2 (arXiv:2405.04434), as
DeepSeek-V2-Lite's published config.json and its ``modeling_deepseek.py``
define the decoder.

Written from the paper and the released code, in straightforward
``jax.numpy`` at ``highest`` matmul precision, with no cache, no kernel and
no batching tricks. It imports nothing of the program under test and takes
nothing it made: the weights are made here again from the seed.

The block (RMSNorm eps 1e-6 throughout, unit scales as initialised):

    h  = RMSNorm(x)
    q  = h Wq -> per head [q_nope (128), q_rope (64)]      no q_lora
    c  = RMSNorm(h Wdkv)                                   latent, 512
    k_rope = YaRN-RoPE(h Wkr)                              one head, shared
    K  = [c W_uk[h], k_rope],  V = c W_uv[h]               per head, materialized
    x  = x + softmax(q K^T * s + causal) V Wo
         s = 192^-0.5 * mscale(40, 0.707)^2
    h2 = RMSNorm(x)
    layer < first_k_dense:  x = x + SwiGLU(h2; 10944)
    otherwise:              p = softmax(h2 Wr) over the router's 64
                            top-6 of p, not renormalised, scale 1
                            x = x + sum over the top-6 claims on experts
                                    held here: p_e SwiGLU_e(h2; 1408)
                                  + SwiGLU_shared(h2; 2 x 1408)
    logits = RMSNorm(x) Wlm                                untied head

YaRN as ``DeepseekV2YarnRotaryEmbedding``: the pairs below the correction
range keep theta's frequency, those above it take theta's over ``factor``,
a linear ramp between; cos and sin are scaled by mscale / mscale_all_dim.

Departures, each shared with the program:

- rotary pairing: the first half of a rope head pairs with the second
  (rotate-half). The released code first de-interleaves its rope columns
  (``view(d/2, 2).transpose``), so the published layout pairs adjacent
  columns; with random weights that is a fixed permutation of the rope
  columns of Wq and Wkr, drawn from the same distribution.
- the expert share: the configuration holds experts 0 .. E-1 of the
  router's width, the part one chip of an expert-parallel group computes.
  Claims on the others are left out, here as in the program.
- routing ties: top-6 is taken of the float32 softmax here; the program
  scores the router in float32 from bfloat16 activations, so a near-tie
  may pick another expert. The limit covers it (``PERF.md``).

Weights of a seed: the model's random initialisation, a truncated normal
on [-2, 2] standard deviations: std 0.02 for the embedding,
1/sqrt(fan_in) for each projection, 1/sqrt(kv_lora_rank) for W_uk and
W_uv. Keys are split as the model's ``init_params`` splits them: (embed,
blocks, head) from the seed's key; one key per layer from the blocks key,
the dense layers first; per layer ten keys, of which 2..7 make Wq, Wdkv,
Wkr, W_uk, W_uv and Wo and 8 the FFN: three keys (gate, up, down) for a
dense layer; four for an MoE layer (router, expert gate, up, down), the
last split again in three for the shared experts' gate, up and down. The
head key makes Wlm.

``quant=True`` computes every projection, the router and the head from
operands rounded to float8 (e4m3) with a scale per row of the activations
and per output column of the weights: the control, one precision step
below the bfloat16 the program computes in.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import common as C

EPS = 1e-6


def static(m: dict) -> tuple:
    """The scalar entries of a config's ``model`` dict, nested groups
    flattened to ``group.key``, hashable for jit."""
    out = []
    for k, v in sorted(m.items()):
        if isinstance(v, dict):
            out += [(f"{k}.{kk}", vv) for kk, vv in sorted(v.items())
                    if isinstance(vv, (bool, int, float, str))]
        elif isinstance(v, (bool, int, float, str)):
            out.append((k, v))
    return tuple(out)


def _keys(m: dict, seed: int):
    k_embed, k_blocks, k_head = jax.random.split(jax.random.PRNGKey(seed), 3)
    return k_embed, k_head, jax.random.split(k_blocks, m["num_layers"])


def _normal(key, shape, std):
    return C.trunc_normal(key, shape, std)


def _dense(key, n_in, n_out):
    return _normal(key, (n_in, n_out), 1.0 / math.sqrt(n_in))


def attention_weights(m: dict, ks) -> dict:
    D, H = m["d_model"], m["num_heads"]
    r, dn = m["mla.kv_lora_rank"], m["mla.qk_nope_head_dim"]
    dr, dv = m["mla.qk_rope_head_dim"], m["mla.v_head_dim"]
    return {"wq": _dense(ks[2], D, H * (dn + dr)),
            "w_dkv": _dense(ks[3], D, r), "w_kr": _dense(ks[4], D, dr),
            "w_uk": _normal(ks[5], (H, r, dn), 1.0 / math.sqrt(r)),
            "w_uv": _normal(ks[6], (H, r, dv), 1.0 / math.sqrt(r)),
            "wo": _dense(ks[7], H * dv, D)}


def dense_ffn_weights(m: dict, key) -> dict:
    D, F = m["d_model"], m["d_ff"]
    kf = jax.random.split(key, 3)
    return {"gate": _dense(kf[0], D, F), "up": _dense(kf[1], D, F),
            "down": _dense(kf[2], F, D)}


def moe_weights(m: dict, key) -> dict:
    D, E = m["d_model"], m["moe.num_experts"]
    F = m["moe.d_ff_expert"]
    R = m.get("moe.router_experts") or E
    k4 = jax.random.split(key, 4)
    w = {"router": _dense(k4[0], D, R),
         "gate": _normal(k4[1], (E, D, F), 1.0 / math.sqrt(D)),
         "up": _normal(k4[2], (E, D, F), 1.0 / math.sqrt(D)),
         "down": _normal(k4[3], (E, F, D), 1.0 / math.sqrt(F))}
    n_sh = m.get("moe.num_shared_experts", 0)
    if n_sh:
        k3 = jax.random.split(k4[3], 3)
        Fs = F * n_sh
        w["shared"] = {"gate": _dense(k3[0], D, Fs),
                       "up": _dense(k3[1], D, Fs),
                       "down": _dense(k3[2], Fs, D)}
    return w


def rmsnorm(x):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + EPS)


def yarn_get_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def inv_freq(m: dict, dim: int) -> tuple[np.ndarray, float]:
    """The rotary frequencies of a rope head of ``dim`` and the factor on
    cos and sin, in float64."""
    base = float(m["rope_theta"])
    freq = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if "rope_scaling.factor" not in m:
        return freq, 1.0
    f = float(m["rope_scaling.factor"])
    orig = m["rope_scaling.original_max_position_embeddings"]

    def corr_dim(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))
                / (2 * math.log(base)))
    low = max(math.floor(corr_dim(m["rope_scaling.beta_fast"])), 0)
    high = min(math.ceil(corr_dim(m["rope_scaling.beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    mask = 1.0 - ramp                       # 1: keep theta's frequency
    freq = freq / f * (1.0 - mask) + freq * mask
    scale = (yarn_get_mscale(f, m["rope_scaling.mscale"])
             / yarn_get_mscale(f, m["rope_scaling.mscale_all_dim"]))
    return freq, scale


def softmax_scale(m: dict) -> float:
    s = (m["mla.qk_nope_head_dim"] + m["mla.qk_rope_head_dim"]) ** -0.5
    all_dim = m.get("rope_scaling.mscale_all_dim", 0)
    if "rope_scaling.factor" in m and all_dim:
        s *= yarn_get_mscale(float(m["rope_scaling.factor"]), all_dim) ** 2
    return s


def rope(m: dict, x):
    """x (B, T, heads, d) at positions 0..T-1, rotate-half pairing."""
    T, d = x.shape[1], x.shape[-1]
    freq, scale = inv_freq(m, d)
    ang = np.arange(T, dtype=np.float64)[:, None] * freq[None]   # (T, d/2)
    cos = jnp.asarray(np.cos(ang) * scale, jnp.float32)[None, :, None]
    sin = jnp.asarray(np.sin(ang) * scale, jnp.float32)[None, :, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def swiglu(h, w, mm):
    return mm(jax.nn.silu(mm(h, w["gate"])) * mm(h, w["up"]), w["down"])


def attention(m: dict, w: dict, h, mm):
    B, T, _ = h.shape
    H = m["num_heads"]
    dn, dr = m["mla.qk_nope_head_dim"], m["mla.qk_rope_head_dim"]
    dv = m["mla.v_head_dim"]
    q = mm(h, w["wq"]).reshape(B, T, H, dn + dr)
    q = jnp.concatenate([q[..., :dn], rope(m, q[..., dn:])], -1)
    c = rmsnorm(mm(h, w["w_dkv"]))                              # (B, T, r)
    k_rope = rope(m, mm(h, w["w_kr"])[:, :, None, :])           # (B,T,1,dr)
    k_nope = jnp.stack([mm(c, w["w_uk"][i]) for i in range(H)], 2)
    v = jnp.stack([mm(c, w["w_uv"][i]) for i in range(H)], 2)   # (B,T,H,dv)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (B, T, H, dr))], -1)
    s = jnp.einsum("bthd,bshd->bhts", q, k) * softmax_scale(m)
    causal = jnp.tril(jnp.ones((T, T), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("bhts,bshd->bthd", p, v).reshape(B, T, H * dv)
    return mm(o, w["wo"])


def moe(m: dict, w: dict, h, mm):
    """The routed experts held here, weighted by their softmax scores,
    plus the shared experts."""
    B, T, D = h.shape
    x = h.reshape(B * T, D)
    E, k = m["moe.num_experts"], m["moe.top_k"]
    probs = jax.nn.softmax(mm(x, w["router"]), axis=-1)        # (N, R)
    vals, idx = jax.lax.top_k(probs, k)
    held = jnp.arange(E)                       # router ids held here
    weight = jnp.sum(jnp.where(idx[:, :, None] == held, vals[:, :, None],
                               0.0), axis=1)                   # (N, E)
    y = jnp.zeros_like(x)
    for e in range(E):
        y = y + weight[:, e:e + 1] * swiglu(
            x, {n: w[n][e] for n in ("gate", "up", "down")}, mm)
    if "shared" in w:
        y = y + swiglu(x, w["shared"], mm)
    return y.reshape(B, T, D)


@partial(jax.jit, static_argnames=("mt", "dense", "quant"))
def block(mt, key, x, *, dense: bool, quant: bool):
    """One decoder block over x (B, T, D); its weights are made from
    ``key`` inside, so a layer's weights live only while it runs."""
    m = dict(mt)
    ks = jax.random.split(key, 10)
    mm = partial(C.matmul, quant=quant)
    x = x + attention(m, attention_weights(m, ks), rmsnorm(x), mm)
    h2 = rmsnorm(x)
    if dense:
        return x + swiglu(h2, dense_ffn_weights(m, ks[8]), mm)
    return x + moe(m, moe_weights(m, ks[8]), h2, mm)


@partial(jax.jit, static_argnames=("mt",))
def _embed(mt, k_embed, tokens):
    m = dict(mt)
    return _normal(k_embed, (m["vocab_size"], m["d_model"]), 0.02)[tokens]


@partial(jax.jit, static_argnames=("mt", "quant"))
def _head(mt, k_head, x, positions, probes, *, quant: bool):
    m = dict(mt)
    xf = rmsnorm(jnp.take_along_axis(x, positions[..., None], axis=1))
    logits = C.matmul(xf, _dense(k_head, m["d_model"], m["vocab_size"]),
                      quant=quant)
    return C.reduce_logits(logits, probes)


def score(m: dict, seed: int, tokens, positions, probes, *,
          quant: bool = False):
    """tokens (B, T) int32; positions (B, P) rows to read; probes (B, K, P)
    token ids to read there. Returns (best (B, P), at_probes (B, K, P),
    argmax (B, P)) of the logits at ``positions``."""
    mt = static(m)
    k_embed, k_head, layer_keys = _keys(m, seed)
    nd = m.get("first_k_dense", 0)
    with jax.default_matmul_precision("highest"):
        x = _embed(mt, k_embed, jnp.asarray(tokens))
        for i in range(m["num_layers"]):
            x = block(mt, layer_keys[i], x, dense=i < nd, quant=quant)
        return _head(mt, k_head, x, jnp.asarray(positions),
                     jnp.asarray(probes), quant=quant)
