"""Plain float32 reference of the OLMo dense decoder (arXiv:2402.00838).

Written from the paper, in straightforward ``jax.numpy`` at ``highest``
matmul precision, with no cache, no kernel and no batching tricks. It
imports nothing of the program under test and takes nothing it made: the
weights are made here again from the seed.

The block, as OLMo-1B publishes it:

    h  = LN(x)                         non-parametric LayerNorm, eps 1e-5
    q, k, v = h Wq, h Wk, h Wv         no biases
    q, k = RoPE(q), RoPE(k)            theta 10000, rotate-half pairing
    x  = x + softmax(q k^T / sqrt(dh) + causal) v Wo
    h2 = LN(x)
    x  = x + (silu(h2 Wgate) * (h2 Wup)) Wdown      SwiGLU
    logits = LN(x) E^T                 tied embeddings, no scaling

Weights of a seed: the model's random initialisation, a truncated normal
on [-2, 2] standard deviations: std 0.02 for the embedding, 1/sqrt(fan_in)
for each projection. The keys are split as the model's ``init_params``
splits them: (embed, blocks, head) from the seed's key, one key per layer
from the blocks key, and per layer eight keys, of which 2..7 make Wq, Wk,
Wv, Wo, Wgate, Wup and 0 makes Wdown.

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

from . import common as C


def _keys(m: dict, seed: int):
    k_embed, k_blocks, _ = jax.random.split(jax.random.PRNGKey(seed), 3)
    return k_embed, jax.random.split(k_blocks, m["num_layers"])


def embedding(m: dict, k_embed):
    return C.trunc_normal(k_embed, (m["vocab_size"], m["d_model"]), 0.02)


def layer_weights(m: dict, key) -> dict:
    D, F = m["d_model"], m["d_ff"]
    Q = m["num_heads"] * m["head_dim"]
    KV = m["num_kv_heads"] * m["head_dim"]
    ks = jax.random.split(key, 8)

    def dense(k, n_in, n_out):
        return C.trunc_normal(k, (n_in, n_out), 1.0 / math.sqrt(n_in))

    return {"wq": dense(ks[2], D, Q), "wk": dense(ks[3], D, KV),
            "wv": dense(ks[4], D, KV), "wo": dense(ks[5], Q, D),
            "w_gate": dense(ks[6], D, F), "w_up": dense(ks[7], D, F),
            "w_down": dense(ks[0], F, D)}


def rope(x, theta: float):
    """x (B, T, heads, dh) at positions 0..T-1; the first half of each head
    pairs with the second."""
    T, dh = x.shape[1], x.shape[-1]
    half = dh // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv      # (T, half)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@partial(jax.jit, static_argnames=("m", "quant"))
def block(m, key, x, *, quant: bool):
    """One decoder block over x (B, T, D); its weights are made from
    ``key`` inside, so a layer's weights live only while it runs."""
    m = dict(m)
    w = layer_weights(m, key)
    mm = partial(C.matmul, quant=quant)
    B, T, _ = x.shape
    H, KV, dh = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    h = C.layernorm(x)
    q = rope(mm(h, w["wq"]).reshape(B, T, H, dh), m["rope_theta"])
    k = rope(mm(h, w["wk"]).reshape(B, T, KV, dh), m["rope_theta"])
    v = mm(h, w["wv"]).reshape(B, T, KV, dh)
    k, v = (jnp.repeat(t, H // KV, axis=2) for t in (k, v))
    s = jnp.einsum("bthd,bshd->bhts", q, k) / math.sqrt(dh)
    causal = jnp.tril(jnp.ones((T, T), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("bhts,bshd->bthd", p, v).reshape(B, T, H * dh)
    x = x + mm(o, w["wo"])
    h2 = C.layernorm(x)
    return x + mm(jax.nn.silu(mm(h2, w["w_gate"])) * mm(h2, w["w_up"]),
                  w["w_down"])


@partial(jax.jit, static_argnames=("m",))
def _embed(m, k_embed, tokens):
    return embedding(dict(m), k_embed)[tokens]


@partial(jax.jit, static_argnames=("m", "quant"))
def _head(m, k_embed, x, positions, probes, *, quant: bool):
    xf = C.layernorm(jnp.take_along_axis(x, positions[..., None], axis=1))
    logits = C.matmul(xf, embedding(dict(m), k_embed).T, quant=quant)
    return C.reduce_logits(logits, probes)


def score(m: dict, seed: int, tokens, positions, probes, *,
          quant: bool = False):
    """tokens (B, T) int32; positions (B, P) rows to read; probes (B, K, P)
    token ids to read there. Returns (best (B, P), at_probes (B, K, P),
    argmax (B, P)) of the logits at ``positions``."""
    mt = C.static(m)
    k_embed, layer_keys = _keys(m, seed)
    with jax.default_matmul_precision("highest"):
        x = _embed(mt, k_embed, jnp.asarray(tokens))
        for i in range(m["num_layers"]):
            x = block(mt, layer_keys[i], x, quant=quant)
        return _head(mt, k_embed, x, jnp.asarray(positions),
                     jnp.asarray(probes), quant=quant)
