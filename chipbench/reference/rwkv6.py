"""Plain float32 reference of RWKV-6 "Finch" (arXiv:2404.05892).

Written from the paper, in straightforward ``jax.numpy`` at ``highest``
matmul precision, token by token through the WKV recurrence, with no
chunking and no cache. It imports nothing of the program under test and
takes nothing it made: the weights are made here again from the seed.

Per block, with x' the previous token's input to the same sub-block (zero
before the first token) and LN a LayerNorm with eps 1e-5; the ranks and the
head norm's eps are the configuration's ``ddlerp_lora_rank`` (32),
``decay_lora_rank`` (64) and ``head_norm_eps``:

    h = LN(x)                                   (ln1, ln1b)
    time mix (data-dependent token shift, "ddlerp"):
        d = h' - h
        lora = tanh((h + d * mu_r) A) reshaped to 5 streams of its rank
        h_s = h + d * (mu_s + lora_s B_s)      s in r, k, v, w, g
        r, k, v = h_r Wr, h_k Wk, h_v Wv; g = silu(h_g Wg)
        w = exp(-exp(w0 + tanh(h_w Aw) Bw))     per-channel decay in (0, 1)
        per head of 64:  y_t = r_t (S + diag(u) k_t^T v_t)
                         S   = diag(w_t) S + k_t^T v_t
        y = GroupNorm(y) over each head (head_norm_eps) * gn + gnb
        x = x + (y * g) Wo
    channel mix:
        h = LN(x) (ln2, ln2b); d = h' - h
        x = x + sigmoid((h + d mu'_r) Wr') * (relu((h + d mu'_k) Wk')^2 Wv')
    logits = LN(x) (ln_f, ln_fb) W_head, after x0 = LN(E[token]) (ln_in)

Departures from the paper, as the configuration runs it: the ddlerp's
shared LoRA input mixes with the receptance's mu (the paper gives it a mu
of its own), and the head GroupNorm's eps is the configuration's (the
program's 1e-5; the published models use 6.4e-4).

Weights of a seed: the model's random initialisation; the keys are split
as the model's ``init_params`` splits them (embed, blocks, head from the
seed's key; one key per layer; sixteen per layer, listed in
``layer_weights``). Norm scales are one and biases zero.

``quant=True`` computes every projection and the head from operands
rounded to float8 (e4m3) with a scale per row of the activations and per
output column of the weights: the control, one precision step below the
bfloat16 the program computes in.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from . import common as C

STREAMS = 5


def _keys(m: dict, seed: int):
    k_embed, k_blocks, k_head = jax.random.split(
        jax.random.PRNGKey(seed), 3)
    return k_embed, jax.random.split(k_blocks, m["num_layers"]), k_head


def layer_weights(m: dict, key) -> dict:
    D, F = m["d_model"], m["d_ff"]
    H, dh = m["num_heads"], m["head_dim"]
    R, RD = m["ddlerp_lora_rank"], m["decay_lora_rank"]
    ks = jax.random.split(key, 16)
    tn = C.trunc_normal

    def dense(k, n_in, n_out):
        return tn(k, (n_in, n_out), 1.0 / math.sqrt(n_in))

    return {
        "mu": tn(ks[0], (STREAMS, D), 0.1),
        "mix_a": tn(ks[1], (D, STREAMS * R), 0.02),
        "mix_b": tn(ks[2], (STREAMS, R, D), 0.02),
        "wr": dense(ks[3], D, D), "wk": dense(ks[4], D, D),
        "wv": dense(ks[5], D, D), "wg": dense(ks[6], D, D),
        "wo": dense(ks[7], D, D),
        "w0": tn(ks[8], (D,), 0.5),
        "decay_a": tn(ks[9], (D, RD), 0.02),
        "decay_b": tn(ks[10], (RD, D), 0.02),
        "u": tn(ks[11], (H, dh), 0.5),
        "mu_ffn": tn(ks[12], (2, D), 0.1),
        "ffn_k": dense(ks[13], D, F), "ffn_v": dense(ks[14], F, D),
        "ffn_r": dense(ks[15], D, D),
    }


def shift(x):
    """The previous token's vector at each position; zero at the first."""
    return jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1]


def wkv(r, k, v, w, u):
    """r, k, v, w (B, T, H, d); u (H, d). Token-sequential recurrence from
    a zero state; returns y (B, T, H, d)."""
    B, T, H, d = r.shape

    def step(S, xs):
        rt, kt, vt, wt = xs                               # (B, H, d)
        kv = kt[..., :, None] * vt[..., None, :]           # (B, H, d, d)
        y = jnp.einsum("bhk,bhkv->bhv", rt, S + u[None, :, :, None] * kv)
        return wt[..., :, None] * S + kv, y

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (r, k, v, w))
    _, ys = lax.scan(step, jnp.zeros((B, H, d, d), jnp.float32), xs)
    return jnp.moveaxis(ys, 0, 1)


@partial(jax.jit, static_argnames=("m", "quant"))
def block(m, key, x, *, quant: bool):
    """One RWKV-6 block over x (B, T, D); weights made from ``key``."""
    m = dict(m)
    w = layer_weights(m, key)
    mm = partial(C.matmul, quant=quant)
    B, T, D = x.shape
    H, dh = m["num_heads"], m["head_dim"]

    h = C.layernorm(x)                       # ln1 = 1, ln1b = 0
    d = shift(h) - h
    lora = jnp.tanh(mm(h + d * w["mu"][0], w["mix_a"]))
    lora = lora.reshape(B, T, STREAMS, m["ddlerp_lora_rank"])
    hs = [h + d * (w["mu"][i] + mm(lora[:, :, i], w["mix_b"][i]))
          for i in range(STREAMS)]
    hr, hk, hv, hw, hg = hs
    r = mm(hr, w["wr"]).reshape(B, T, H, dh)
    k = mm(hk, w["wk"]).reshape(B, T, H, dh)
    v = mm(hv, w["wv"]).reshape(B, T, H, dh)
    g = jax.nn.silu(mm(hg, w["wg"]))
    decay = jnp.exp(-jnp.exp(
        w["w0"] + mm(jnp.tanh(mm(hw, w["decay_a"])), w["decay_b"])))
    y = wkv(r, k, v, decay.reshape(B, T, H, dh), w["u"])
    y = C.layernorm(y, eps=m["head_norm_eps"]).reshape(B, T, D)  # gn = 1
    x = x + mm(y * g, w["wo"])

    h = C.layernorm(x)                       # ln2 = 1, ln2b = 0
    d = shift(h) - h
    kk = jnp.square(jax.nn.relu(mm(h + d * w["mu_ffn"][0], w["ffn_k"])))
    rr = jax.nn.sigmoid(mm(h + d * w["mu_ffn"][1], w["ffn_r"]))
    return x + rr * mm(kk, w["ffn_v"])


@partial(jax.jit, static_argnames=("m",))
def _embed(m, k_embed, tokens):
    m = dict(m)
    e = C.trunc_normal(k_embed, (m["vocab_size"], m["d_model"]), 0.02)
    return C.layernorm(e[tokens])            # ln_in = 1, ln_inb = 0


@partial(jax.jit, static_argnames=("m", "quant"))
def _head(m, k_head, x, positions, probes, *, quant: bool):
    m = dict(m)
    D = m["d_model"]
    w = C.trunc_normal(k_head, (D, m["vocab_size"]), 1.0 / math.sqrt(D))
    xf = C.layernorm(jnp.take_along_axis(x, positions[..., None], axis=1))
    return C.reduce_logits(C.matmul(xf, w, quant=quant), probes)


def score(m: dict, seed: int, tokens, positions, probes, *,
          quant: bool = False):
    """tokens (B, T) int32; positions (B, P) rows to read; probes (B, K, P)
    token ids to read there. Returns (best (B, P), at_probes (B, K, P),
    argmax (B, P)) of the logits at ``positions``."""
    mt = C.static(m)
    k_embed, layer_keys, k_head = _keys(m, seed)
    with jax.default_matmul_precision("highest"):
        x = _embed(mt, k_embed, jnp.asarray(tokens))
        for i in range(m["num_layers"]):
            x = block(mt, layer_keys[i], x, quant=quant)
        return _head(mt, k_head, x, jnp.asarray(positions),
                     jnp.asarray(probes), quant=quant)
