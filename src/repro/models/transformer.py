"""Dense decoder-only transformer.

Covers: codeqwen1.5-7b, olmo-1b, command-r-35b, command-r-plus-104b and the
qwen2-vl-7b backbone (M-RoPE + patch-embedding injection; vision frontend is
a stub per the task spec).

Block params are stacked on a leading layer axis and executed with
jax.lax.scan; an optional remat policy wraps the block body. The same block
runs train (full seq), prefill (full seq + returns KV), and decode (S=1 +
cache update).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from collections.abc import Callable
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from . import layers as L


# --- params ----------------------------------------------------------------------

def init_params(cfg, key):
    D, F, V = cfg.d_model, cfg.d_ff, cfg.vocab_size
    Q, KV = cfg.q_dim, cfg.kv_dim
    norm_init, _ = L.make_norm(cfg)
    k_embed, k_blocks, k_head = jax.random.split(key, 3)

    def block_init(k):
        ks = jax.random.split(k, 8)
        p = {
            "ln1": norm_init(ks[0], D),
            "ln2": norm_init(ks[1], D),
            "wq": L.dense_init(ks[2], D, Q),
            "wk": L.dense_init(ks[3], D, KV),
            "wv": L.dense_init(ks[4], D, KV),
            "wo": L.dense_init(ks[5], Q, D),
            "w_gate": L.dense_init(ks[6], D, F),
            "w_up": L.dense_init(ks[7], D, F),
            "w_down": L.dense_init(ks[0], F, D),
        }
        if cfg.qkv_bias:
            p["bq"] = jnp.zeros((Q,), L.PARAM_DTYPE)
            p["bk"] = jnp.zeros((KV,), L.PARAM_DTYPE)
            p["bv"] = jnp.zeros((KV,), L.PARAM_DTYPE)
        return p

    blocks = jax.vmap(block_init)(jax.random.split(k_blocks, cfg.num_layers))
    params = {
        "embed": L.trunc_normal(k_embed, (V, D)),
        "blocks": blocks,
        "ln_f": norm_init(k_head, D),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L.dense_init(k_head, D, V)
    if cfg.family == "vlm":
        params["patch_proj"] = L.dense_init(k_head, D, D)
    return params


# --- block -----------------------------------------------------------------------

def _rope(cfg, x, batch):
    if cfg.mrope:
        return L.apply_mrope(x, batch["pos3"], cfg.rope_theta)
    return L.apply_rope(x, batch["positions"], cfg.rope_theta)


def _block(cfg, p, x, batch, mask, cache=None, cache_pos=None,
           constrain=None, kv_expand=1):
    """One decoder block. cache: (k, v) with shape (B, T, KV*e, dh) or
    None. Returns (y, (k_full, v_full)) where k_full/v_full include the
    cache. kv_expand replicates KV heads for TP-aligned serving."""
    _, norm = L.make_norm(cfg)
    B, S, D = x.shape
    dh = cfg.head_dim
    cd = L.COMPUTE_DTYPE

    h = norm(x, p["ln1"]).astype(cd)
    q = h @ p["wq"].astype(cd)
    k = h @ p["wk"].astype(cd)
    v = h @ p["wv"].astype(cd)
    if cfg.qkv_bias:
        q, k, v = (q + p["bq"].astype(cd), k + p["bk"].astype(cd),
                   v + p["bv"].astype(cd))
    q = q.reshape(B, S, cfg.num_heads, dh)
    k = k.reshape(B, S, cfg.num_kv_heads, dh)
    v = v.reshape(B, S, cfg.num_kv_heads, dh)
    q = _rope(cfg, q, batch)
    k = _rope(cfg, k, batch)

    if cache is not None:
        ck, cv = cache
        k = lax.dynamic_update_slice(ck, L.expand_kv(k, kv_expand)
                                     .astype(ck.dtype), (0, cache_pos, 0, 0))
        v = lax.dynamic_update_slice(cv, L.expand_kv(v, kv_expand)
                                     .astype(cv.dtype), (0, cache_pos, 0, 0))

    if mask is None:       # long sequence: never materialize (S, T) scores
        attn = L.chunked_attention(q, k.astype(cd), v.astype(cd),
                                   causal=True)
    else:
        attn = L.gqa_attention(q, k.astype(cd), v.astype(cd), mask=mask)
    if constrain is not None:
        attn = constrain(attn)
    y = x + (attn.reshape(B, S, cfg.q_dim) @ p["wo"].astype(cd)).astype(x.dtype)

    h2 = norm(y, p["ln2"]).astype(cd)
    ff = L.swiglu(h2, p["w_gate"].astype(cd), p["w_up"].astype(cd),
                  p["w_down"].astype(cd))
    out = y + ff.astype(x.dtype)
    if constrain is not None:
        out = constrain(out)
    return out, (k, v)


# --- embedding / head ---------------------------------------------------------------

def _embed(cfg, params, batch):
    x = params["embed"].astype(L.COMPUTE_DTYPE)[batch["tokens"]]
    if cfg.family == "vlm" and "patch_embeds" in batch:
        pe = batch["patch_embeds"].astype(L.COMPUTE_DTYPE) \
            @ params["patch_proj"].astype(L.COMPUTE_DTYPE)
        P = pe.shape[1]
        x = jnp.concatenate([pe, x[:, P:]], axis=1)
    return x


def _head(cfg, params, x):
    _, norm = L.make_norm(cfg)
    h = norm(x, params["ln_f"]).astype(L.COMPUTE_DTYPE)
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return (h @ w.astype(L.COMPUTE_DTYPE)).astype(jnp.float32)


def _default_batch(cfg, batch):
    b = dict(batch)
    B, S = b["tokens"].shape
    if "positions" not in b:
        b["positions"] = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32),
                                          (B, S))
    if cfg.mrope and "pos3" not in b:
        b["pos3"] = jnp.broadcast_to(b["positions"][None], (3, B, S))
    return b


# --- full-sequence forward (train / prefill) ------------------------------------------

def forward(cfg, params, batch, *, remat=False, constrain=None,
            return_kv=False):
    batch = _default_batch(cfg, batch)
    x = _embed(cfg, params, batch)
    B, S, D = x.shape
    mask = L.causal_mask(S, S) if S <= L.ATTN_CHUNK_THRESHOLD else None

    def body(carry, p):
        y, kv = _block(cfg, p, carry, batch, mask, constrain=constrain)
        return y, (kv if return_kv else 0)

    if remat:
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.nothing_saveable)
    x, kvs = lax.scan(body, x, params["blocks"])
    logits = _head(cfg, params, x)
    return (logits, kvs) if return_kv else logits


def loss_fn(cfg, params, batch, *, remat=True, constrain=None):
    logits = forward(cfg, params, batch, remat=remat, constrain=constrain)
    loss = L.softmax_xent(logits, batch["labels"])
    return jnp.mean(loss)


# --- decode ---------------------------------------------------------------------------

@dataclasses.dataclass
class DecodeState:
    k: jax.Array           # (L, B, T, KV, dh)
    v: jax.Array
    pos: jax.Array         # scalar int32: next write offset


jax.tree_util.register_dataclass(DecodeState, data_fields=["k", "v", "pos"],
                                 meta_fields=[])


def init_decode_state(cfg, batch_size: int, cache_len: int,
                      dtype=L.COMPUTE_DTYPE, kv_expand=1) -> DecodeState:
    shape = (cfg.num_layers, batch_size, cache_len,
             cfg.num_kv_heads * kv_expand, cfg.head_dim)
    return DecodeState(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
                       pos=jnp.zeros((), jnp.int32))


def prefill(cfg, params, batch, cache_len: int, *, constrain=None,
            kv_expand=1):
    """Run the full prompt, materialize the KV cache, return last logits."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    logits, kvs = forward(cfg, params, batch, return_kv=True,
                          constrain=constrain)
    k, v = kvs                                 # (L, B, S, KV, dh)
    if kv_expand > 1:                          # expand on the head axis (3)
        k, v = (jnp.repeat(t, kv_expand, axis=3) for t in (k, v))
    pad = [(0, 0), (0, 0), (0, cache_len - S), (0, 0), (0, 0)]
    state = DecodeState(k=jnp.pad(k.astype(L.COMPUTE_DTYPE), pad),
                        v=jnp.pad(v.astype(L.COMPUTE_DTYPE), pad),
                        pos=jnp.array(S, jnp.int32))
    return logits[:, -1], state


def decode_step(cfg, params, state: DecodeState, tokens, *, constrain=None):
    """One token for the whole batch. tokens: (B,) int32."""
    B = tokens.shape[0]
    T = state.k.shape[2]
    kv_expand = state.k.shape[3] // cfg.num_kv_heads
    pos = state.pos
    batch = {"tokens": tokens[:, None],
             "positions": jnp.full((B, 1), pos, jnp.int32)}
    batch = _default_batch(cfg, batch)
    x = _embed(cfg, params, batch)
    # valid keys: cache slots < pos, plus the slot we are writing now.
    kj = jnp.arange(T)[None, :]
    mask = (kj <= pos)[None, None, None]

    def body(carry, xs):
        p, ck, cv = xs
        y, (k_full, v_full) = _block(cfg, p, carry, batch, mask,
                                     cache=(ck, cv), cache_pos=pos,
                                     constrain=constrain,
                                     kv_expand=kv_expand)
        return y, (k_full, v_full)

    x, (k_new, v_new) = lax.scan(body, x, (params["blocks"], state.k, state.v))
    logits = _head(cfg, params, x)[:, 0]
    return logits, DecodeState(k=k_new, v=v_new, pos=pos + 1)


# --- paged decode (continuous batching) -----------------------------------------
# Per-slot lengths instead of one lockstep position: every slot in the
# batch can sit at a different point of a different request, and cache
# bytes track live tokens through the page pool (runtime/kv_pager.py).


@dataclasses.dataclass
class PagedDecodeState:
    k_pages: jax.Array     # (L, KV, P, page, dh); page 0 = trash page
    v_pages: jax.Array


jax.tree_util.register_dataclass(PagedDecodeState,
                                 data_fields=["k_pages", "v_pages"],
                                 meta_fields=[])


def init_paged_decode_state(cfg, num_pages: int, page_size: int,
                            dtype=L.COMPUTE_DTYPE) -> PagedDecodeState:
    k, v = L.paged_cache_init(cfg.num_layers, num_pages, page_size,
                              cfg.num_kv_heads, cfg.head_dim, dtype)
    return PagedDecodeState(k_pages=k, v_pages=v)


def paged_prefill(cfg, params, batch, lengths, *, constrain=None):
    """Forward the (padded) prompts; return per-sequence last-live-token
    logits plus the raw per-layer KV (L, B, S, KV, dh) for page scatter.

    tokens (B, S) may be padded past lengths (B,): causality keeps pad
    positions from touching live ones, and the pad KV is either masked by
    the live length or scattered to the trash page.
    """
    logits, (k, v) = forward(cfg, params, batch, return_kv=True,
                             constrain=constrain)
    idx = (lengths - 1)[:, None, None]
    last = jnp.take_along_axis(logits, idx, axis=1)[:, 0]
    return last, (k.astype(L.COMPUTE_DTYPE), v.astype(L.COMPUTE_DTYPE))


def write_prefill_pages(cfg, state: PagedDecodeState, kv, page_ids
                        ) -> PagedDecodeState:
    """Scatter one prefilled request's KV into its pages. kv: (k, v) each
    (L, S, KV, dh), S a page multiple; page_ids (S/page,) int32 with dead
    entries pointing at the trash page."""
    k, v = kv
    return PagedDecodeState(
        k_pages=L.paged_cache_write_prompt(state.k_pages, k, page_ids),
        v_pages=L.paged_cache_write_prompt(state.v_pages, v, page_ids))


def copy_kv_page(state: PagedDecodeState, src, dst) -> PagedDecodeState:
    """Duplicate one physical page (all layers, K and V) — the
    copy-on-write step when a request is about to append into a page
    other requests still share. src/dst: scalar int32 page ids."""
    return PagedDecodeState(
        k_pages=state.k_pages.at[:, :, dst].set(state.k_pages[:, :, src]),
        v_pages=state.v_pages.at[:, :, dst].set(state.v_pages[:, :, src]))


def paged_prefill_shared(cfg, params, state: PagedDecodeState, batch,
                         lengths, prefix_pages, prefix_len, *,
                         constrain=None):
    """Prefill only the suffix past a shared prefix already resident in
    the page pool.

    tokens (B, S) hold the suffix from the divergence token on (padded
    past ``lengths``); ``prefix_pages`` (B, Mp) are the full pages
    holding each row's shared prefix (dead entries -> trash page) and
    ``prefix_len`` (B,) its token count (a page multiple). The cached
    prefix KV was RoPE'd at its absolute positions when first written,
    so suffix queries attend it directly — exactly like decode reading
    the cache — while suffix positions are offset by ``prefix_len``.
    Returns per-row last-live-suffix-token logits plus the raw suffix
    KV (L, B, S, KV, dh) for the usual page scatter.
    """
    B, S = batch["tokens"].shape
    page = state.k_pages.shape[3]
    Mp = prefix_pages.shape[1]
    Tp = Mp * page                       # static gathered-prefix length
    KV, dh = cfg.num_kv_heads, cfg.head_dim
    lengths = lengths.astype(jnp.int32)
    prefix_len = prefix_len.astype(jnp.int32)

    batch = dict(batch)
    batch["positions"] = (prefix_len[:, None]
                          + jnp.arange(S, dtype=jnp.int32))
    batch = _default_batch(cfg, batch)
    x = _embed(cfg, params, batch)

    # cache layout per layer: [gathered prefix (Tp) | suffix slots (S)]
    # keys:  prefix entries valid below prefix_len, suffix causal
    qi = jnp.arange(S)[None, :, None]
    kj = jnp.arange(Tp + S)[None, None, :]
    pl = prefix_len[:, None, None]
    mask = jnp.where(kj < Tp, kj < pl, kj - Tp <= qi)[:, None, None]

    def gather(pages):                   # (L, KV, P, pg, dh) -> (L,B,Tp,..)
        g = pages[:, :, prefix_pages]    # (L, KV, B, Mp, pg, dh)
        g = g.reshape(g.shape[0], KV, B, Tp, dh)
        return jnp.moveaxis(g, 1, 3)     # (L, B, Tp, KV, dh)

    pk, pv = gather(state.k_pages), gather(state.v_pages)
    zeros = jnp.zeros((B, S, KV, dh), pk.dtype)

    def body(carry, xs):
        p, lk, lv = xs
        ck = jnp.concatenate([lk, zeros], axis=1)
        cv = jnp.concatenate([lv, zeros], axis=1)
        y, (k_full, v_full) = _block(cfg, p, carry, batch, mask,
                                     cache=(ck, cv), cache_pos=Tp,
                                     constrain=constrain)
        return y, (k_full[:, Tp:], v_full[:, Tp:])

    x, (k, v) = lax.scan(body, x, (params["blocks"], pk, pv))
    logits = _head(cfg, params, x)
    idx = (lengths - 1)[:, None, None]
    last = jnp.take_along_axis(logits, idx, axis=1)[:, 0]
    return last, (k.astype(L.COMPUTE_DTYPE), v.astype(L.COMPUTE_DTYPE))


def _paged_block(cfg, p, x, batch, k_pages, v_pages, layer, page_table,
                 page_ids, offsets, attn_lengths, constrain=None):
    """One decoder block over a paged cache, S == 1. k/v_pages: every
    layer's (L, KV, P, page, dh) pool, read and written at ``layer`` in
    place; returns (y, k_pages, v_pages) with the new token appended at
    (page_ids, offsets)."""
    from ..kernels import ops as kops

    _, norm = L.make_norm(cfg)
    B, S, D = x.shape
    dh = cfg.head_dim
    cd = L.COMPUTE_DTYPE

    h = norm(x, p["ln1"]).astype(cd)
    q = h @ p["wq"].astype(cd)
    k = h @ p["wk"].astype(cd)
    v = h @ p["wv"].astype(cd)
    if cfg.qkv_bias:
        q, k, v = (q + p["bq"].astype(cd), k + p["bk"].astype(cd),
                   v + p["bv"].astype(cd))
    q = q.reshape(B, S, cfg.num_heads, dh)
    k = k.reshape(B, S, cfg.num_kv_heads, dh)
    v = v.reshape(B, S, cfg.num_kv_heads, dh)
    q = _rope(cfg, q, batch)
    k = _rope(cfg, k, batch)

    k_pages, v_pages = kops.paged_kv_append(k_pages, v_pages, k[:, 0],
                                            v[:, 0], layer, page_ids, offsets)
    attn = kops.paged_decode_attention(q[:, 0], k_pages, v_pages,
                                       page_table, attn_lengths, layer)
    if constrain is not None:
        attn = constrain(attn[:, None])[:, 0]
    y = x + (attn.reshape(B, 1, cfg.q_dim)
             @ p["wo"].astype(cd)).astype(x.dtype)

    h2 = norm(y, p["ln2"]).astype(cd)
    ff = L.swiglu(h2, p["w_gate"].astype(cd), p["w_up"].astype(cd),
                  p["w_down"].astype(cd))
    out = y + ff.astype(x.dtype)
    if constrain is not None:
        out = constrain(out)
    return out, k_pages, v_pages


def paged_decode_step(cfg, params, state: PagedDecodeState, tokens,
                      page_table, lengths, active, *, constrain=None):
    """One token per slot against the paged cache.

    tokens (B,) int32; page_table (B, M) int32; lengths (B,) live context
    per slot; active (B,) bool — inactive slots write to the trash page
    and read zero-length caches, so their (discarded) outputs cost no
    correctness. Returns (logits (B, V), new state); lengths are advanced
    by the caller (host-side scheduler owns them).
    """
    B = tokens.shape[0]
    page = state.k_pages.shape[3]
    lengths = lengths.astype(jnp.int32)
    active = active.astype(bool)
    batch = _default_batch(cfg, {"tokens": tokens[:, None],
                                 "positions": lengths[:, None]})
    x = _embed(cfg, params, batch)

    slot = (lengths // page)[:, None]                       # (B, 1)
    page_ids = jnp.take_along_axis(page_table, slot, axis=1)[:, 0]
    page_ids = jnp.where(active, page_ids, 0)               # trash page
    offsets = jnp.where(active, lengths % page, 0)
    attn_lengths = jnp.where(active, lengths + 1, 0)        # incl. new token

    def body(carry, p):
        # the pools ride in the carry, so each layer's append and read land
        # in place; as xs/ys the scan would slice and restack them
        x, kp, vp, layer = carry
        y, kp, vp = _paged_block(cfg, p, x, batch, kp, vp, layer,
                                 page_table, page_ids, offsets, attn_lengths,
                                 constrain=constrain)
        return (y, kp, vp, layer + 1), None

    (x, k_pages, v_pages, _), _ = lax.scan(
        body, (x, state.k_pages, state.v_pages, jnp.zeros((), jnp.int32)),
        params["blocks"])
    logits = _head(cfg, params, x)[:, 0]
    return logits, PagedDecodeState(k_pages=k_pages, v_pages=v_pages)


def paged_decode_multi(cfg, params, state: PagedDecodeState, pending,
                       lengths, remaining, page_table, mask, h, *,
                       hmax: int, teacher=None):
    """Up to ``h`` fused ``paged_decode_step``s with on-device sampling
    (layers.multi_step_decode): one dispatch and one host sync per
    horizon. The engine clamps ``h`` at page boundaries, so the page
    table is constant for the whole fused run."""
    def step(s, toks, pt, lens, act):
        return paged_decode_step(cfg, params, s, toks, pt, lens, act)
    return L.multi_step_decode(step, hmax, state, pending, lengths,
                               remaining, page_table, mask, h, teacher)
