"""MoE decoder models: olmoe-1b-7b (GQA + 64e top-8) and
deepseek-v2-lite-16b (MLA latent attention with YaRN rope; a leading dense
layer, then 2 shared / 64 routed top-6).

MLA decode uses the *absorbed* formulation: the KV cache stores only the
compressed latent (kv_lora_rank + rope head) per token — the paper-adjacent
"pack the stationary operand small" idea applied to the KV cache — and
W_uk / W_uv are folded into the query/output projections at decode time.

Layers ``0 .. first_k_dense - 1`` carry a dense SwiGLU of ``d_ff`` in
``params["dense"]``; the MoE layers after them are stacked in
``params["blocks"]`` and run in one ``lax.scan``. Serving (``prefill``,
``paged_prefill``, the decode steps) routes dropless; ``forward`` keeps
the capacity factor for training.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax import lax

from . import layers as L
from .transformer import _default_batch, _embed, _head


# --- params ----------------------------------------------------------------------

def init_params(cfg, key):
    """Keys as the reference regenerates them: (embed, blocks, head) from
    ``key``, one key per layer from the blocks key (dense layers first),
    ten per layer, of which 2..7 make the attention and 8 the FFN."""
    D, V = cfg.d_model, cfg.vocab_size
    norm_init, _ = L.make_norm(cfg)
    k_embed, k_blocks, k_head = jax.random.split(key, 3)

    def block_init(k, dense=False):
        ks = jax.random.split(k, 10)
        p = {"ln1": norm_init(ks[0], D), "ln2": norm_init(ks[1], D)}
        if cfg.mla is not None:
            m = cfg.mla
            H = cfg.num_heads
            p["wq"] = L.dense_init(
                ks[2], D, H * (m.qk_nope_head_dim + m.qk_rope_head_dim))
            p["w_dkv"] = L.dense_init(ks[3], D, m.kv_lora_rank)
            p["w_kr"] = L.dense_init(ks[4], D, m.qk_rope_head_dim)
            p["kv_ln"] = jnp.ones((m.kv_lora_rank,), L.PARAM_DTYPE)
            p["w_uk"] = L.trunc_normal(
                ks[5], (H, m.kv_lora_rank, m.qk_nope_head_dim),
                std=1.0 / math.sqrt(m.kv_lora_rank))
            p["w_uv"] = L.trunc_normal(
                ks[6], (H, m.kv_lora_rank, m.v_head_dim),
                std=1.0 / math.sqrt(m.kv_lora_rank))
            p["wo"] = L.dense_init(ks[7], H * m.v_head_dim, D)
        else:
            p["wq"] = L.dense_init(ks[2], D, cfg.q_dim)
            p["wk"] = L.dense_init(ks[3], D, cfg.kv_dim)
            p["wv"] = L.dense_init(ks[4], D, cfg.kv_dim)
            p["wo"] = L.dense_init(ks[5], cfg.q_dim, D)
        if dense:
            kf = jax.random.split(ks[8], 3)
            p["mlp"] = {"w_gate": L.dense_init(kf[0], D, cfg.d_ff),
                        "w_up": L.dense_init(kf[1], D, cfg.d_ff),
                        "w_down": L.dense_init(kf[2], cfg.d_ff, D)}
        else:
            p["moe"] = L.init_moe_params(ks[8], cfg, D)
        return p

    keys = jax.random.split(k_blocks, cfg.num_layers)
    nd = cfg.first_k_dense
    out = {
        "embed": L.trunc_normal(k_embed, (V, D)),
        "blocks": jax.vmap(block_init)(keys[nd:]),
        "ln_f": norm_init(k_head, D),
        "lm_head": L.dense_init(k_head, D, V),
    }
    if nd:
        out["dense"] = jax.vmap(lambda k: block_init(k, dense=True))(
            keys[:nd])
    return out


def softmax_scale(cfg) -> float:
    """MLA's score scale: 1/sqrt(nope + rope head), times YaRN's mscale
    squared where the rope is scaled with ``mscale_all_dim``."""
    m = cfg.mla
    scale = 1.0 / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    rs = cfg.rope_scaling
    if rs is not None and rs.mscale_all_dim:
        scale *= L.yarn_mscale(rs.factor, rs.mscale_all_dim) ** 2
    return scale


# --- attention variants ------------------------------------------------------------

def _gqa_part(cfg, p, h, batch, mask, cache, cache_pos):
    B, S, _ = h.shape
    cd = L.COMPUTE_DTYPE
    dh = cfg.head_dim
    q = (h @ p["wq"].astype(cd)).reshape(B, S, cfg.num_heads, dh)
    k = (h @ p["wk"].astype(cd)).reshape(B, S, cfg.num_kv_heads, dh)
    v = (h @ p["wv"].astype(cd)).reshape(B, S, cfg.num_kv_heads, dh)
    q = L.apply_rope(q, batch["positions"], cfg.rope_theta,
                     cfg.rope_scaling)
    k = L.apply_rope(k, batch["positions"], cfg.rope_theta,
                     cfg.rope_scaling)
    if cache is not None:
        ck, cv = cache
        k = lax.dynamic_update_slice(ck, k.astype(ck.dtype),
                                     (0, cache_pos, 0, 0))
        v = lax.dynamic_update_slice(cv, v.astype(cv.dtype),
                                     (0, cache_pos, 0, 0))
    if mask is None:       # long sequence: never materialize (S, T) scores
        attn = L.chunked_attention(q, k.astype(cd), v.astype(cd),
                                   causal=True)
    else:
        attn = L.gqa_attention(q, k.astype(cd), v.astype(cd), mask=mask)
    out = attn.reshape(B, S, cfg.q_dim) @ p["wo"].astype(cd)
    return out, (k, v)


def _mla_part(cfg, p, h, batch, mask, cache, cache_pos):
    """Multi-head latent attention (training/prefill: materialized K/V;
    decode: absorbed latent math — see `_mla_decode_part`)."""
    m = cfg.mla
    B, S, _ = h.shape
    H = cfg.num_heads
    cd = L.COMPUTE_DTYPE
    dn, dr, dv = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim

    q = (h @ p["wq"].astype(cd)).reshape(B, S, H, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = L.apply_rope(q_rope, batch["positions"], cfg.rope_theta,
                          cfg.rope_scaling)

    c_kv = L.rmsnorm(h @ p["w_dkv"].astype(cd), p["kv_ln"])   # (B,S,r)
    k_rope = L.apply_rope((h @ p["w_kr"].astype(cd))[:, :, None, :],
                          batch["positions"], cfg.rope_theta,
                          cfg.rope_scaling)                     # (B,S,1,dr)

    latent = jnp.concatenate([c_kv, k_rope[:, :, 0, :]], axis=-1)
    if cache is not None:
        latent = lax.dynamic_update_slice(cache, latent.astype(cache.dtype),
                                          (0, cache_pos, 0))
        c_all = latent[..., :m.kv_lora_rank].astype(cd)
        kr_all = latent[..., m.kv_lora_rank:].astype(cd)
    else:
        c_all, kr_all = c_kv, k_rope[:, :, 0, :]

    # absorbed scores: q_nope (B,S,H,dn) @ w_uk^T (H,dn,r) -> (B,S,H,r)
    q_lat = jnp.einsum("bshd,hrd->bshr", q_nope,
                       p["w_uk"].astype(cd))
    scale = softmax_scale(cfg)

    def scores_chunk(ql, qr, q0, qc):
        s = (jnp.einsum("bshr,btr->bhst", ql, c_all)
             + jnp.einsum("bshd,btd->bhst", qr, kr_all))
        s = s.astype(jnp.float32) * scale
        if mask is not None:
            s = jnp.where(mask, s, L.NEG_INF)
        elif cache is None:     # full-seq causal mask built per chunk
            qi = (q0 + jnp.arange(qc))[:, None]
            kj = jnp.arange(c_all.shape[1])[None, :]
            s = jnp.where((kj <= qi)[None, None], s, L.NEG_INF)
        probs = jax.nn.softmax(s, axis=-1).astype(cd)
        return jnp.einsum("bhst,btr->bshr", probs, c_all)      # (B,S,H,r)

    if S > L.ATTN_CHUNK_THRESHOLD:   # chunked: never materialize (S, T)
        qc = math.gcd(S, 1024)
        n = S // qc

        def body(carry, xs):
            ql, qr, i = xs
            return carry, scores_chunk(ql, qr, i * qc, qc)

        qls = q_lat.reshape(B, n, qc, H, -1).swapaxes(0, 1)
        qrs = q_rope.reshape(B, n, qc, H, -1).swapaxes(0, 1)
        _, outs = lax.scan(body, 0, (qls, qrs, jnp.arange(n)))
        out_lat = outs.swapaxes(0, 1).reshape(B, S, H, -1)
    else:
        out_lat = scores_chunk(q_lat, q_rope, 0, S)
    attn = jnp.einsum("bshr,hrd->bshd", out_lat, p["w_uv"].astype(cd))
    out = attn.reshape(B, S, H * dv) @ p["wo"].astype(cd)
    return out, latent


# --- block ------------------------------------------------------------------------

def _ffn_tail(cfg, p, y, dims, return_keep=False):
    """Second half of every block (lockstep AND paged decode share this,
    so shared-expert / dispatch changes cannot diverge the paths): norm ->
    the dense SwiGLU of a leading layer, or the routed expert FFN (+
    shared experts) -> residual. ``return_keep`` appends the (B, S, k)
    mask of the claims computed on experts held here (None for a dense
    layer)."""
    _, norm = L.make_norm(cfg)
    B, S, D = y.shape
    cd = L.COMPUTE_DTYPE
    h2 = norm(y, p["ln2"]).astype(cd).reshape(B * S, D)
    keep = None
    if "mlp" in p:
        mp = jax.tree.map(lambda a: a.astype(cd), p["mlp"])
        ff = L.swiglu(h2, mp["w_gate"], mp["w_up"], mp["w_down"])
        aux = jnp.zeros((), jnp.float32)
    else:
        mp = jax.tree.map(lambda a: a.astype(cd), p["moe"])
        out = L.moe_ffn(h2, mp, dims, return_keep=return_keep)
        ff, aux = out[0], out[1]
        if return_keep:
            keep = out[2].reshape(B, S, -1)
        if cfg.moe.num_shared_experts:
            ff = ff + L.swiglu(h2, mp["shared_gate"], mp["shared_up"],
                               mp["shared_down"])
    res = y + ff.reshape(B, S, D).astype(y.dtype)
    if return_keep:
        return res, aux, keep
    return res, aux


def _block(cfg, p, x, batch, mask, dims, cache=None, cache_pos=None,
           constrain=None):
    _, norm = L.make_norm(cfg)
    cd = L.COMPUTE_DTYPE
    h = norm(x, p["ln1"]).astype(cd)
    if cfg.mla is not None:
        # MLA mask shape: (B?,H? broadcast) (.., S, T) -> (1,1,S,T)
        mla_mask = mask[:, :, 0] if mask is not None and mask.ndim == 5 \
            else mask
        attn_out, kv = _mla_part(cfg, p, h, batch, mla_mask, cache, cache_pos)
    else:
        attn_out, kv = _gqa_part(cfg, p, h, batch, mask, cache, cache_pos)
    if constrain is not None:
        attn_out = constrain(attn_out)
    y = x + attn_out.astype(x.dtype)
    out, aux = _ffn_tail(cfg, p, y, dims)
    if constrain is not None:
        out = constrain(out)
    return out, kv, aux


# --- forward / loss ------------------------------------------------------------------

def forward(cfg, params, batch, *, remat=False, constrain=None,
            return_kv=False, return_aux=False, dropless=False):
    """Logits over ``batch``; ``return_kv`` appends every layer's cache
    entries in layer order (MLA: latents (L, B, S, r+dr); GQA: (K, V)).
    Training routes with the capacity factor (``moe_dims``); serving
    passes ``dropless`` (``moe_dims_dropless``): no claim on a held
    expert is ever dropped, so a prompt's output does not depend on its
    bucket padding."""
    batch = _default_batch(cfg, batch)
    x = _embed(cfg, params, batch)
    B, S, D = x.shape
    mask = L.causal_mask(S, S) if S <= L.ATTN_CHUNK_THRESHOLD else None
    dims = (L.moe_dims_dropless if dropless else L.moe_dims)(cfg, B * S)

    def body(carry, p):
        y, kv, aux = _block(cfg, p, carry, batch, mask, dims,
                            constrain=constrain)
        return y, (kv if return_kv else 0, aux)

    if remat:
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.nothing_saveable)
    dense_kvs = None
    if cfg.first_k_dense:
        x, (dense_kvs, _) = lax.scan(body, x, params["dense"])
    x, (kvs, auxs) = lax.scan(body, x, params["blocks"])
    logits = _head(cfg, params, x)
    aux = jnp.mean(auxs)
    out = [logits]
    if return_kv:       # dense layers first, along the layer axis
        out.append(kvs if dense_kvs is None else jax.tree.map(
            lambda a, b: jnp.concatenate([a, b]), dense_kvs, kvs))
    if return_aux:
        out.append(aux)
    return tuple(out) if len(out) > 1 else logits


def loss_fn(cfg, params, batch, *, remat=True, constrain=None,
            aux_coef=0.01):
    logits, aux = forward(cfg, params, batch, remat=remat,
                          constrain=constrain, return_aux=True)
    loss = jnp.mean(L.softmax_xent(logits, batch["labels"]))
    return loss + aux_coef * aux


# --- decode -----------------------------------------------------------------------

@dataclasses.dataclass
class MoEDecodeState:
    kv: jax.Array          # GQA: stacked (2, L, B, T, KV, dh); MLA: (L,B,T,r+dr)
    pos: jax.Array


jax.tree_util.register_dataclass(MoEDecodeState, data_fields=["kv", "pos"],
                                 meta_fields=[])


def init_decode_state(cfg, batch_size: int, cache_len: int,
                      dtype=L.COMPUTE_DTYPE, kv_expand=1) -> MoEDecodeState:
    assert kv_expand == 1, "olmoe KV=16 divides tp; MLA caches latents"  
    if cfg.mla is not None:
        width = cfg.mla.kv_lora_rank + cfg.mla.qk_rope_head_dim
        kv = jnp.zeros((cfg.num_layers, batch_size, cache_len, width), dtype)
    else:
        kv = jnp.zeros((2, cfg.num_layers, batch_size, cache_len,
                        cfg.num_kv_heads, cfg.head_dim), dtype)
    return MoEDecodeState(kv=kv, pos=jnp.zeros((), jnp.int32))


def prefill(cfg, params, batch, cache_len: int, *, constrain=None,
            kv_expand=1):
    tokens = batch["tokens"]
    B, S = tokens.shape
    logits, kvs, _ = forward(cfg, params, batch, return_kv=True,
                             return_aux=True, constrain=constrain,
                             dropless=True)
    if cfg.mla is not None:
        pad = [(0, 0), (0, 0), (0, cache_len - S), (0, 0)]
        kv = jnp.pad(kvs.astype(L.COMPUTE_DTYPE), pad)
    else:
        k, v = kvs
        pad = [(0, 0), (0, 0), (0, cache_len - S), (0, 0), (0, 0)]
        kv = jnp.stack([jnp.pad(k.astype(L.COMPUTE_DTYPE), pad),
                        jnp.pad(v.astype(L.COMPUTE_DTYPE), pad)])
    return logits[:, -1], MoEDecodeState(kv=kv, pos=jnp.array(S, jnp.int32))


def decode_step(cfg, params, state: MoEDecodeState, tokens, *,
                constrain=None):
    B = tokens.shape[0]
    pos = state.pos
    nd = cfg.first_k_dense
    T = state.kv.shape[-2] if cfg.mla is not None else state.kv.shape[-3]
    batch = _default_batch(cfg, {"tokens": tokens[:, None],
                                 "positions": jnp.full((B, 1), pos,
                                                       jnp.int32)})
    x = _embed(cfg, params, batch)
    kj = jnp.arange(T)[None, :]
    mask5 = (kj <= pos)[None, None, None]     # (1,1,1,1,T)
    # decode batches mix independent requests: dropless capacity keeps a
    # slot's output independent of which neighbours share its step
    dims = L.moe_dims_dropless(cfg, B)

    if cfg.mla is not None:
        def body(carry, xs):
            p, cache = xs
            y, kv, _ = _block(cfg, p, carry, batch, mask5, dims,
                              cache=cache, cache_pos=pos)
            return y, kv
        caches = (state.kv[:nd], state.kv[nd:]) if nd else (None, state.kv)
    else:
        def body(carry, xs):
            p, ck, cv = xs
            y, (k, v), _ = _block(cfg, p, carry, batch, mask5, dims,
                                  cache=(ck, cv), cache_pos=pos)
            return y, (k, v)
        caches = ((state.kv[:, :nd], state.kv[:, nd:]) if nd
                  else (None, state.kv))

    def run(x, stack, kv):
        if cfg.mla is not None:
            return lax.scan(body, x, (stack, kv))
        x, (k_new, v_new) = lax.scan(body, x, (stack, kv[0], kv[1]))
        return x, jnp.stack([k_new, v_new])

    dense_new = None
    if nd:
        x, dense_new = run(x, params["dense"], caches[0])
    x, kv_new = run(x, params["blocks"], caches[1])
    if dense_new is not None:
        kv_new = jnp.concatenate([dense_new, kv_new],
                                 axis=0 if cfg.mla is not None else 1)
    logits = _head(cfg, params, x)[:, 0]
    return logits, MoEDecodeState(kv=kv_new, pos=pos + 1)


# --- paged latent decode (continuous batching) ------------------------------------
# MLA's absorbed decode already stores only the compressed latent
# (kv_lora_rank + rope head) per token; the paged serving path pools
# those latent rows — pages are (page, kv_lora_rank + rope) slabs, NOT
# per-head K/V — so cache bytes track live tokens at latent width and
# the page table grows linearly like the dense transformer's.


def latent_width(cfg) -> int:
    return cfg.mla.kv_lora_rank + cfg.mla.qk_rope_head_dim


@dataclasses.dataclass
class MoEPagedState:
    """The latent page pools. A pool of n layers is (P, n * page * (r+dr)):
    row ``page`` holds that page for every layer, each layer's tokens'
    latent rows laid end to end at column ``layer * page * (r+dr)``. The
    prefill's page writes are whole rows, the decode's token writes and
    table gathers are one layer's columns of a row, and the pool keeps a
    row-major layout on the chip. Page 0 is the trash page."""
    kv_pages: jax.Array     # the MoE layers' pool
    dense_pages: jax.Array  # the first_k_dense layers' own pool
    claims: jax.Array       # () int32: live slots' claims on held experts
    #                         since the decode dispatch began


jax.tree_util.register_dataclass(
    MoEPagedState, data_fields=["kv_pages", "dense_pages", "claims"],
    meta_fields=[])


def init_paged_decode_state(cfg, num_pages: int, page_size: int,
                            dtype=L.COMPUTE_DTYPE) -> MoEPagedState:
    assert cfg.mla is not None, "paged decode pools the MLA latent cache"
    nd = cfg.first_k_dense

    def pool(n):
        return jnp.zeros((num_pages, n * page_size * latent_width(cfg)),
                         dtype)
    return MoEPagedState(kv_pages=pool(cfg.num_layers - nd),
                         dense_pages=pool(nd),
                         claims=jnp.zeros((), jnp.int32))


def paged_prefill(cfg, params, batch, lengths, *, constrain=None):
    """Forward the (padded) prompts; return per-sequence last-live-token
    logits plus the raw per-layer latents (L, B, S, r+dr) for page
    scatter. Routing is dropless, so trailing pads neither displace a
    live token from an expert nor change its output: one program serves
    every prompt length of a bucket."""
    logits, kvs, _ = forward(cfg, params, batch, return_kv=True,
                             return_aux=True, constrain=constrain,
                             dropless=True)
    idx = (lengths - 1)[:, None, None]
    last = jnp.take_along_axis(logits, idx, axis=1)[:, 0]
    return last, kvs.astype(L.COMPUTE_DTYPE)


def write_prefill_pages(cfg, state: MoEPagedState, latents, page_ids
                        ) -> MoEPagedState:
    """Scatter one prefilled request's latents into its pages. latents:
    (L, S, r+dr) in layer order, S a page multiple; page_ids (S/page,)
    int32 with dead entries pointing at the trash page."""
    nd = cfg.first_k_dense
    n_pages = page_ids.shape[0]

    def write(pool, lat):
        rows = lat.reshape(lat.shape[0], n_pages, -1).swapaxes(0, 1)
        return pool.at[page_ids].set(
            rows.reshape(n_pages, -1).astype(pool.dtype))

    return dataclasses.replace(state,
                               kv_pages=write(state.kv_pages, latents[nd:]),
                               dense_pages=write(state.dense_pages,
                                                 latents[:nd]))


def _mla_paged_block(cfg, p, x, batch, pool, col, page_w, page_table,
                     page_ids, offsets, pos, active, dims):
    """One MLA block over the paged latent cache, S == 1. pool: a
    ``MoEPagedState`` pool whose columns ``col .. col + page_w - 1`` (a
    page of latent rows) are this block's layer; the new token's latent is written at
    (page_ids, offsets) in place before the absorbed-score gather of the
    slots' table pages. Returns the block's output, the pool and the
    live slots' claims on held experts (0 for a dense layer)."""
    m = cfg.mla
    _, norm = L.make_norm(cfg)
    B, S, D = x.shape
    H = cfg.num_heads
    cd = L.COMPUTE_DTYPE
    dn, dr, dv = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    r = m.kv_lora_rank

    h = norm(x, p["ln1"]).astype(cd)
    q = (h @ p["wq"].astype(cd)).reshape(B, 1, H, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = L.apply_rope(q_rope, batch["positions"], cfg.rope_theta,
                          cfg.rope_scaling)
    c_kv = L.rmsnorm(h @ p["w_dkv"].astype(cd), p["kv_ln"])     # (B,1,r)
    k_rope = L.apply_rope((h @ p["w_kr"].astype(cd))[:, :, None, :],
                          batch["positions"], cfg.rope_theta,
                          cfg.rope_scaling)                     # (B,1,1,dr)
    latent = jnp.concatenate([c_kv[:, 0], k_rope[:, 0, 0]], axis=-1)
    w = latent.shape[-1]
    # one (1, r+dr) window per slot: element-wise indices would make the
    # compiler flatten the pool
    pool = lax.scatter(
        pool, jnp.stack([page_ids, col + offsets * w], axis=-1),
        latent.astype(pool.dtype), lax.ScatterDimensionNumbers(
            update_window_dims=(1,), inserted_window_dims=(0,),
            scatter_dims_to_operand_dims=(0, 1)),
        mode=lax.GatherScatterMode.PROMISE_IN_BOUNDS)

    starts = jnp.stack([page_table, jnp.broadcast_to(col, page_table.shape)],
                       axis=-1)
    g = lax.gather(                             # (B, M, page * (r+dr))
        pool, starts, lax.GatherDimensionNumbers(
            offset_dims=(2,), collapsed_slice_dims=(0,),
            start_index_map=(0, 1)),
        slice_sizes=(1, page_w),
        mode=lax.GatherScatterMode.PROMISE_IN_BOUNDS)
    T = g.shape[1] * g.shape[2] // w
    g = g.reshape(B, T, w).astype(cd)
    c_all, kr_all = g[..., :r], g[..., r:]
    q_lat = jnp.einsum("bshd,hrd->bshr", q_nope, p["w_uk"].astype(cd))
    s = (jnp.einsum("bshr,btr->bhst", q_lat, c_all)
         + jnp.einsum("bshd,btd->bhst", q_rope, kr_all))
    s = s.astype(jnp.float32) * softmax_scale(cfg)
    # linear page table: entry (row, off) holds absolute position
    # row*page + off, so "<= pos" is the whole validity story (rows past
    # the live pages are trash but their positions already exceed pos);
    # inactive slots run with pos = 0, attending to one garbage entry
    kj = jnp.arange(T)[None, :]
    s = jnp.where((kj <= pos[:, None])[:, None, None, :], s, L.NEG_INF)
    probs = jax.nn.softmax(s, axis=-1).astype(cd)
    out_lat = jnp.einsum("bhst,btr->bshr", probs, c_all)
    attn = jnp.einsum("bshr,hrd->bshd", out_lat, p["w_uv"].astype(cd))
    y = x + (attn.reshape(B, 1, H * dv) @ p["wo"].astype(cd)) \
        .astype(x.dtype)
    out, _, keep = _ffn_tail(cfg, p, y, dims, return_keep=True)
    claims = (jnp.zeros((), jnp.int32) if keep is None
              else jnp.sum(keep[:, 0] & active[:, None], dtype=jnp.int32))
    return out, pool, claims


def paged_decode_step(cfg, params, state: MoEPagedState, tokens,
                      page_table, lengths, active, *, constrain=None):
    """One token per slot against the paged latent cache. tokens (B,)
    int32; page_table (B, M) int32; lengths (B,) live context per slot;
    active (B,) bool — inactive slots write to the trash page and read a
    single masked entry. Lengths are advanced by the caller; the live
    slots' claims on held experts are added to ``state.claims``."""
    del constrain
    assert cfg.mla is not None
    B = tokens.shape[0]
    page_w = state.kv_pages.shape[1] // (cfg.num_layers - cfg.first_k_dense)
    page = page_w // latent_width(cfg)
    pos = jnp.where(active, lengths.astype(jnp.int32), 0)
    batch = _default_batch(cfg, {"tokens": tokens[:, None],
                                 "positions": pos[:, None]})
    x = _embed(cfg, params, batch)
    slot = (pos // page)[:, None]
    page_ids = jnp.take_along_axis(page_table, slot, axis=1)[:, 0]
    page_ids = jnp.where(active, page_ids, 0)
    offsets = jnp.where(active, pos % page, 0)
    # dropless decode capacity: see decode_step — slots are independent
    # requests, so batch composition must never cause an expert drop
    dims = L.moe_dims_dropless(cfg, B)

    def layers(x, claims, stack, pool):
        """The blocks of ``stack`` over their own pool, which rides in the
        scan's carry so each layer's write lands in place."""
        def body(carry, p):
            x, claims, pool, col = carry
            y, pool, c = _mla_paged_block(cfg, p, x, batch, pool, col,
                                          page_w, page_table, page_ids,
                                          offsets, pos, active, dims)
            return (y, claims + c, pool, col + page_w), None
        (x, claims, pool, _), _ = lax.scan(
            body, (x, claims, pool, jnp.zeros((), jnp.int32)), stack)
        return x, claims, pool

    claims, dense_pages = state.claims, state.dense_pages
    if cfg.first_k_dense:
        x, claims, dense_pages = layers(x, claims, params["dense"],
                                        dense_pages)
    x, claims, kv_pages = layers(x, claims, params["blocks"],
                                 state.kv_pages)
    logits = _head(cfg, params, x)[:, 0]
    return logits, MoEPagedState(kv_pages=kv_pages, dense_pages=dense_pages,
                                 claims=claims)


def expert_rows_per_step(cfg, num_slots: int) -> int:
    """Expert rows one decode step computes for ``num_slots`` slots: every
    MoE layer runs each held expert at the dropless capacity."""
    dims = L.moe_dims_dropless(cfg, num_slots)
    return ((cfg.num_layers - cfg.first_k_dense) * dims.num_experts
            * dims.capacity)


def paged_decode_multi(cfg, params, state: MoEPagedState, pending,
                       lengths, remaining, page_table, mask, h, *,
                       hmax: int, teacher=None):
    """Up to ``h`` fused ``paged_decode_step``s against the latent pages
    (layers.multi_step_decode) with on-device sampling. ``state.claims``
    counts the dispatch's claims on held experts from zero."""
    def step(s, toks, pt, lens, act):
        return paged_decode_step(cfg, params, s, toks, pt, lens, act)
    state = dataclasses.replace(state, claims=jnp.zeros((), jnp.int32))
    return L.multi_step_decode(step, hmax, state, pending, lengths,
                               remaining, page_table, mask, h, teacher)
