"""Pure-jnp oracles for every Pallas kernel in this package.

Each oracle defines the *semantics* the kernel must match bit-for-bit
(up to accumulation-order tolerance). Tests sweep shapes/dtypes and
``assert_allclose`` kernel-vs-oracle with ``interpret=True``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


# --- packed canvas (multi-layer block-packed MVM) -------------------------------

def packed_canvas(x_packed: jax.Array, w_virtual: jax.Array) -> jax.Array:
    """y = x_packed (B, R) @ W_virtual (R, C) — the kernel's semantics.

    W_virtual is the dense virtual plane (zeros outside the tiles); the
    kernel computes the same product touching only the occupied blocks.
    """
    return (x_packed.astype(jnp.float32)
            @ w_virtual.astype(jnp.float32)).astype(x_packed.dtype)


def blocks_to_dense(w_blocks: jax.Array, meta, R: int, C: int) -> jax.Array:
    """Reconstruct W_virtual (R, C) from compacted blocks + meta (4, G).

    Inverse of the planner's build_w_blocks; used to cross-check that the
    compacted storage plus oracle matmul equals the per-tile matmuls.
    """
    import numpy as np
    meta = np.asarray(meta)
    w = np.zeros((R, C), np.float32)
    for g in range(meta.shape[1]):
        kb, cb = int(meta[0, g]), int(meta[1, g])
        w[kb * 128:(kb + 1) * 128, cb * 128:(cb + 1) * 128] = \
            np.asarray(w_blocks[g], np.float32)
    return jnp.asarray(w)


# --- grouped MVM (MoE expert GEMM) -----------------------------------------------

def grouped_mvm(x: jax.Array, w: jax.Array) -> jax.Array:
    """x: (E, C, D), w: (E, D, F) -> (E, C, F). f32 accumulation."""
    return jnp.einsum("ecd,edf->ecf", x.astype(jnp.float32),
                      w.astype(jnp.float32)).astype(x.dtype)


# --- flash attention (causal GQA, prefill/train) ----------------------------------

def mha_attention(q, k, v, *, causal=True, window=0, scale=None):
    """q: (B, S, H, dh); k/v: (B, T, KV, dh); grouped-query; f32 softmax.

    window > 0 limits attention to the last `window` positions (local attn).
    Query position i is aligned to key position i + (T - S) (suffix queries).
    """
    B, S, H, dh = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    scale = scale if scale is not None else 1.0 / math.sqrt(dh)
    qg = q.reshape(B, S, KV, G, dh).astype(jnp.float32)
    logits = jnp.einsum("bskgd,btkd->bkgst", qg, k.astype(jnp.float32))
    logits *= scale
    qi = jnp.arange(S)[:, None] + (T - S)
    kj = jnp.arange(T)[None, :]
    mask = jnp.ones((S, T), bool)
    if causal:
        mask &= kj <= qi
    if window:
        mask &= kj > qi - window
    logits = jnp.where(mask[None, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v.astype(jnp.float32))
    return out.reshape(B, S, H, dh).astype(q.dtype)


# --- decode attention (single query, KV cache with live length) -------------------

def decode_attention(q, k, v, lengths, *, scale=None):
    """q: (B, H, dh); k/v: (B, T, KV, dh); lengths: (B,) valid cache length.

    Query attends to cache positions < lengths[b]. f32 softmax. A row with
    length 0 has no valid keys and yields zeros (the kernels' flush guard).
    """
    B, H, dh = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    scale = scale if scale is not None else 1.0 / math.sqrt(dh)
    qg = q.reshape(B, KV, G, dh).astype(jnp.float32)
    logits = jnp.einsum("bkgd,btkd->bkgt", qg, k.astype(jnp.float32)) * scale
    valid = (jnp.arange(T)[None, :] < lengths[:, None])[:, None, None, :]
    logits = jnp.where(valid, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgt,btkd->bkgd", probs, v.astype(jnp.float32))
    out = jnp.where(lengths[:, None, None, None] > 0, out, 0.0)
    return out.reshape(B, H, dh).astype(q.dtype)


def paged_kv_append(pages, new, layer, page_ids, offsets):
    """pages (L, KV, P, page, dh); new (B, KV, dh): row ``offsets[b]`` of
    page ``page_ids[b]`` of layer ``layer`` takes new[b] (an XLA scatter)."""
    heads = jnp.arange(pages.shape[1])[None, :]
    return pages.at[layer, heads, page_ids[:, None], offsets[:, None]].set(
        new.astype(pages.dtype))


def paged_decode_attention(q, k_pages, v_pages, page_table, lengths, *,
                           scale=None):
    """q: (B, H, dh); k/v_pages: (P, page, KV, dh); page_table: (B, M).

    Gathers each sequence's pages into a contiguous (B, M*page, KV, dh)
    cache and applies decode_attention — the semantics the paged kernel
    must match while touching only the owned pages.
    """
    B = q.shape[0]
    M = page_table.shape[1]
    P, page, KV, dh = k_pages.shape
    k = k_pages[page_table].reshape(B, M * page, KV, dh)
    v = v_pages[page_table].reshape(B, M * page, KV, dh)
    return decode_attention(q, k, v, lengths, scale=scale)
