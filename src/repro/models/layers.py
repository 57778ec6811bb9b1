"""Shared model building blocks (pure JAX, no framework deps).

Conventions:
  * params are nested dicts of jnp arrays; block params are STACKED along a
    leading layer axis and consumed with jax.lax.scan (O(1) HLO per model).
  * params live in f32; compute runs in bf16 (cast at use). Logits in f32.
  * every function is shape-polymorphic over batch/seq so the same code
    serves train_step (full seq), prefill, and decode (seq=1 + cache).
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

COMPUTE_DTYPE = jnp.bfloat16
PARAM_DTYPE = jnp.float32

Params = Any  # nested dict pytree


# --- sharding hints ---------------------------------------------------------------
# SPMD propagation cannot infer useful shardings through data-dependent
# gather/scatter (MoE dispatch) — without hints it replicates the big
# intermediates. The launcher installs the mesh axis names at trace time;
# outside a mesh context the hints are no-ops, so CPU smoke tests and
# oracle comparisons run the identical code path.

import contextvars as _cv

_SHARD_CTX: _cv.ContextVar = _cv.ContextVar("repro_shard_ctx", default=None)


def set_shard_ctx(dp_axes, tp_axis: str | None, dp_size: int = 1,
                  tp_size: int = 1):
    """Returns a contextvar token; pass to reset_shard_ctx afterwards."""
    return _SHARD_CTX.set({"dp": dp_axes, "tp": tp_axis,
                           "dp_size": dp_size, "tp_size": tp_size})


def reset_shard_ctx(token):
    _SHARD_CTX.reset(token)


def shard_hint(x, *dims: str | None):
    """Constrain x to P(...), mapping 'dp'/'tp' to the installed axes.

    Uneven sharding (dim not divisible by the axis) is allowed — XLA
    pads — and measurably beats forced replication (qwen2-vl's 28 heads
    over 16 chips: 11.6 s vs 34.4 s collective). A dim SMALLER than its
    axis is dropped (mostly-empty shards lose to replication)."""
    ctx = _SHARD_CTX.get()
    if ctx is None:
        return x
    spec = []
    for i, d in enumerate(dims):
        if d and x.shape[i] >= ctx.get(f"{d}_size", 1):
            spec.append(ctx[d])
        else:
            spec.append(None)
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.PartitionSpec(*spec))


def dp_group_count() -> int:
    """Number of data-parallel groups for group-local MoE dispatch."""
    ctx = _SHARD_CTX.get()
    return int(ctx.get("dp_size", 1)) if ctx else 1


def tp_divides(n: int) -> bool:
    """True when a head-axis hint for n heads is worthwhile: the model
    axis must be installed and n must at least fill it (uneven is fine —
    see shard_hint; fewer heads than chips is not)."""
    ctx = _SHARD_CTX.get()
    tp = int(ctx.get("tp_size", 1)) if ctx else 1
    return tp > 1 and n >= tp


def serve_kv_expand(cfg, tp: int) -> int:
    """KV-head replication factor for serving under tensor parallelism.

    Storing each KV head e times makes the cache head axis divide the
    model axis, aligning every chip's q heads with exactly its resident
    KV heads — no per-step cache resharding (the SPMD partitioner
    otherwise falls back to 'involuntary full rematerialization' of the
    cache slice every layer). Returns 1 when expansion can't align
    (then the cache shards over dh instead).
    """
    import math as _m
    kv, h = cfg.num_kv_heads, cfg.num_heads
    if cfg.mla is not None or kv == 0:
        return 1
    e = tp // _m.gcd(kv, tp)
    if e > 1 and (kv * e) % tp == 0 and h % (kv * e) == 0 and e <= tp:
        return e
    return 1


def expand_kv(k, e: int):
    """(B, S, KV, dh) -> (B, S, KV*e, dh); q head h maps to expanded head
    h // (G/e), preserving grouping (jnp.repeat is contiguous)."""
    return k if e == 1 else jnp.repeat(k, e, axis=2)


# --- paged KV cache ---------------------------------------------------------------
# Fixed-size pages from a shared pool; each sequence names its pages via an
# int32 page table row, so cache memory tracks *live* tokens instead of
# batch x max_len — the serving analogue of the paper's packed canvas
# (occupied blocks only), with page 0 reserved as the pager's trash page.

def paged_cache_init(num_layers: int, num_pages: int, page_size: int,
                     kv_heads: int, head_dim: int, dtype=COMPUTE_DTYPE):
    """(k_pages, v_pages), each (L, KV, P, page, dh).

    Pools live in the *kernel* layout (head-major). The dense decode step
    carries them whole through its layer scan and both paged kernels
    (kernels.ops.paged_kv_append, paged_decode_attention) write and read
    them in place, so they cross a step without relayout. Slicing a layer
    out for ``paged_cache_append`` beside the kernel breaks this: the
    compiler then keeps the pool token-major for the scatter and relays it
    out around each kernel call, O(pool bytes) of HBM traffic per layer
    and step."""
    shape = (num_layers, kv_heads, num_pages, page_size, head_dim)
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


def paged_cache_append(pages, new, page_ids, offsets):
    """Write one token per sequence into its page: pages (KV, P, page,
    dh), new (B, KV, dh), page_ids/offsets (B,) int32. Inactive slots must
    point at the trash page (collisions there are harmless)."""
    return pages.at[:, page_ids, offsets].set(
        new.transpose(1, 0, 2).astype(pages.dtype))


def paged_cache_write_prompt(pages, kv, page_ids):
    """Scatter a prefilled sequence into its pages: pages (L, KV, P, page,
    dh), kv (L, S, KV, dh) with S a page multiple, page_ids (S/page,) int32
    (entries past the live pages point at the trash page)."""
    Lc, KVh, P, page, dh = pages.shape
    chunks = kv.reshape(Lc, -1, page, KVh, dh).transpose(0, 3, 1, 2, 4)
    return pages.at[:, :, page_ids].set(chunks.astype(pages.dtype))


# --- initializers -------------------------------------------------------------

def trunc_normal(key, shape, std=0.02, dtype=PARAM_DTYPE):
    return std * jax.random.truncated_normal(key, -2.0, 2.0, shape, dtype)


def dense_init(key, d_in, d_out, dtype=PARAM_DTYPE):
    return trunc_normal(key, (d_in, d_out), std=1.0 / math.sqrt(d_in),
                        dtype=dtype)


# --- norms ----------------------------------------------------------------------

def rmsnorm(x, scale=None, eps=1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    if scale is not None:
        x = x * scale.astype(jnp.float32)
    return x.astype(dt)


def layernorm(x, scale=None, bias=None, eps=1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    x = (x - mu) * lax.rsqrt(var + eps)
    if scale is not None:
        x = x * scale.astype(jnp.float32)
    if bias is not None:
        x = x + bias.astype(jnp.float32)
    return x.astype(dt)


def make_norm(cfg):
    """Returns (init_fn(key)->params|None, apply_fn(x, p)->x)."""
    if cfg.norm == "rmsnorm":
        return (lambda key, d: jnp.ones((d,), PARAM_DTYPE),
                lambda x, p: rmsnorm(x, p))
    if cfg.norm == "layernorm":
        return (lambda key, d: jnp.ones((d,), PARAM_DTYPE),
                lambda x, p: layernorm(x, p))
    # olmo: non-parametric LN — no learnable affine at all
    return (lambda key, d: jnp.zeros((0,), PARAM_DTYPE),
            lambda x, p: layernorm(x, None))


# --- rotary embeddings ----------------------------------------------------------

def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention-temperature factor (DeepSeek-V2 ``yarn_get_mscale``)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_range(scaling, head_dim: int, theta: float) -> tuple[int, int]:
    """The rotary pairs YaRN ramps between extrapolation (below ``low``)
    and interpolation by ``factor`` (above ``high``): the pairs that turn
    ``beta_fast`` and ``beta_slow`` times over the original context."""
    def dim(rotations):
        return (head_dim * math.log(scaling.original_max_position_embeddings
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    return (max(math.floor(dim(scaling.beta_fast)), 0),
            min(math.ceil(dim(scaling.beta_slow)), head_dim - 1))


def rope_freqs(head_dim: int, theta: float, scaling=None):
    """Inverse frequencies of the ``head_dim // 2`` rotary pairs; with a
    YaRN ``scaling`` (configs.RopeScaling), blended between theta's and
    theta's over ``factor`` by a linear ramp over ``yarn_range``."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    if scaling is None:
        return freqs
    lo, hi = yarn_range(scaling, head_dim, theta)
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - lo)
                    / (hi - lo if hi > lo else 0.001), 0.0, 1.0)
    return freqs / scaling.factor * ramp + freqs * (1.0 - ramp)


def apply_rope(x, positions, theta, scaling=None):
    """x: (B, S, H, dh); positions: (B, S) int32. ``scaling`` is a
    configs.RopeScaling (YaRN) or None for plain RoPE."""
    half = x.shape[-1] // 2
    freqs = rope_freqs(x.shape[-1], theta, scaling)           # (half,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B,S,half)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    if scaling is not None:
        m = (yarn_mscale(scaling.factor, scaling.mscale)
             / yarn_mscale(scaling.factor, scaling.mscale_all_dim))
        if m != 1.0:
            cos, sin = cos * m, sin * m
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def mrope_sections(head_dim: int) -> tuple[int, int, int]:
    """Qwen2-VL M-RoPE splits the half-dim into (temporal, height, width)
    sections; for dh=128 the reference split is (16, 24, 24)."""
    half = head_dim // 2
    t = half // 4
    h = (half - t) // 2
    return t, h, half - t - h


def apply_mrope(x, positions3, theta):
    """x: (B, S, H, dh); positions3: (3, B, S) int32 (t/h/w position ids)."""
    half = x.shape[-1] // 2
    freqs = rope_freqs(x.shape[-1], theta)
    secs = mrope_sections(x.shape[-1])
    angle_parts = []
    off = 0
    for i, s in enumerate(secs):
        a = positions3[i][..., None].astype(jnp.float32) * freqs[off:off + s]
        angle_parts.append(a)
        off += s
    angles = jnp.concatenate(angle_parts, axis=-1)            # (B,S,half)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# --- attention core --------------------------------------------------------------

NEG_INF = -1e30


def gqa_attention(q, k, v, *, mask=None, scale=None):
    """Grouped-query attention.

    q: (B, S, H, dh); k, v: (B, T, KV, dh); H % KV == 0.
    mask: broadcastable to (B, 1, 1, S, T) or (B, KV, G, S, T); True = keep.
    """
    B, S, H, dh = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    scale = scale if scale is not None else 1.0 / math.sqrt(dh)
    # §Perf iteration C1: pin the (KV, G) head factorization to the model
    # axis — without the hints SPMD cannot map the flat-head sharding of
    # q onto the cache's KV-head sharding and falls back to replicating
    # the cache slice every layer ("involuntary full rematerialization").
    # Only when KV divides the axis: a dropped-dim constraint would FORCE
    # replication and pessimize the non-divisible-head archs (qwen2-vl,
    # whisper) — measured +3x collective before the guard.
    qg = q.reshape(B, S, KV, G, dh)
    if tp_divides(KV):
        qg = shard_hint(qg, "dp", None, "tp", None, None)
        k = shard_hint(k, "dp", None, "tp", None)
        v = shard_hint(v, "dp", None, "tp", None)
    logits = jnp.einsum("bskgd,btkd->bkgst", qg, k) * scale
    logits = logits.astype(jnp.float32)
    if tp_divides(KV):
        logits = shard_hint(logits, "dp", "tp", None, None, None)
    if mask is not None:
        logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v)
    return out.reshape(B, S, H, dh)


def chunked_attention(q, k, v, *, causal=True, window=0, scale=None,
                      q_chunk=1024, q_offset=0):
    """GQA attention that never materializes the full (S, T) score matrix.

    lax.scan over query chunks; per-chunk scores are (B, H, q_chunk, T) —
    the pure-JAX analogue of the Pallas flash kernel, used for long
    sequences where (S, T) would not fit (prefill_32k etc.). Masks are
    built per chunk from iota, never as an (S, T) array.

    K/V are expanded from KV to H heads first (the standard replicate-KV-
    across-TP move): the head axis then shards cleanly over the model
    axis, keeping the per-chunk score tensor distributed; contracting a
    dh-sharded layout instead would replicate it (psum per chunk).

    q: (B, S, H, dh); k, v: (B, T, KV, dh). Query row i is at absolute
    position q_offset + i; key j at position j.
    """
    B, S, H, dh = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    scale = scale if scale is not None else 1.0 / math.sqrt(dh)
    if S % q_chunk:
        q_chunk = math.gcd(S, q_chunk) or S
    n = S // q_chunk
    if G > 1:
        k = jnp.repeat(k, G, axis=2)               # (B, T, H, dh)
        v = jnp.repeat(v, G, axis=2)
    # head-axis hints only when H divides the model axis — a dropped-dim
    # constraint would force head replication (see gqa_attention note)
    if tp_divides(H):
        k = shard_hint(k, "dp", None, "tp", None)
        v = shard_hint(v, "dp", None, "tp", None)
        q = shard_hint(q, "dp", None, "tp", None)
    qg = q.reshape(B, n, q_chunk, H, dh).transpose(1, 0, 3, 2, 4)
    kj = jnp.arange(T)[None, :]

    def chunk(carry, xs):
        qc, i = xs                                 # (B, H, qc, dh)
        logits = jnp.einsum("bhsd,bthd->bhst", qc, k) * scale
        logits = logits.astype(jnp.float32)
        if tp_divides(H):
            logits = shard_hint(logits, "dp", "tp", None, None)
        qi = (i * q_chunk + jnp.arange(q_chunk))[:, None] + q_offset
        m = jnp.ones((q_chunk, T), bool)
        if causal:
            m = m & (kj <= qi)
        if window:
            m = m & (kj > qi - window)
        logits = jnp.where(m[None, None], logits, NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
        out = jnp.einsum("bhst,bthd->bhsd", probs, v)
        return carry, out

    _, outs = lax.scan(chunk, 0, (qg, jnp.arange(n)))
    # outs: (n, B, H, q_chunk, dh) -> (B, S, H, dh)
    out = outs.transpose(1, 0, 3, 2, 4).reshape(B, S, H, dh)
    return out


# sequences longer than this use chunked attention in the model zoo
ATTN_CHUNK_THRESHOLD = 2048


def causal_mask(s: int, t: int, *, q_offset=0):
    """(1,1,1,S,T) boolean causal mask; q position i attends to j <= i+off."""
    qi = jnp.arange(s)[:, None] + q_offset
    kj = jnp.arange(t)[None, :]
    return (kj <= qi)[None, None, None]


def window_mask(s: int, t: int, window: int, *, q_offset=0):
    qi = jnp.arange(s)[:, None] + q_offset
    kj = jnp.arange(t)[None, :]
    return ((kj <= qi) & (kj > qi - window))[None, None, None]


def valid_mask_from_length(t: int, length):
    """(B,1,1,1,T): cache positions < length are valid (decode)."""
    kj = jnp.arange(t)[None, :]
    return (kj < length[:, None])[:, None, None, None, :]


# --- FFN -----------------------------------------------------------------------

def swiglu(x, w_gate, w_up, w_down):
    h = jax.nn.silu(x @ w_gate) * (x @ w_up)
    return h @ w_down


def gelu_mlp(x, w_up, b_up, w_down, b_down):
    h = jax.nn.gelu(x @ w_up + b_up, approximate=True)
    return h @ w_down + b_down


# --- MoE (capacity-based dispatch; EP-shardable over the expert axis) ------------

@dataclasses.dataclass(frozen=True)
class MoEDims:
    """``num_experts`` experts held here, router ids ``first ..
    first + num_experts - 1``, each taking at most ``capacity`` claims."""
    num_experts: int
    top_k: int
    capacity: int
    first: int = 0


def moe_dims(cfg, n_tokens: int) -> MoEDims:
    """Expert-capacity ceiling for routing ``n_tokens`` tokens in training
    (GShard's capacity factor; overflowing claims drop). Serving routes
    dropless (``moe_dims_dropless``)."""
    m = cfg.moe
    # an expert's share of the claims is of the router's width, however
    # many of its experts are held here
    cap = int(math.ceil(n_tokens / m.router_width * m.capacity_factor
                        * m.top_k))
    cap = max(cap, 4)
    # align capacity to the MXU lane quantum: this is the IMC-paper's
    # "tile fits the D_i x D_o plane" rule transplanted to the TPU.
    cap = (cap + 127) // 128 * 128 if n_tokens >= 128 else cap
    return MoEDims(m.num_experts, m.top_k, cap)


def moe_dims_dropless(cfg, n_tokens: int) -> MoEDims:
    """Dims whose capacity no routing pattern can overflow: every expert
    can absorb all ``n_tokens`` (a token claims an expert at most once).
    Serving routes so at prefill and at decode, as DeepSeek-V2's released
    inference code does: a token's output never depends on which other
    tokens share its batch, its bucket padding, or its step."""
    m = cfg.moe
    return MoEDims(m.num_experts, m.top_k, max(n_tokens, 4))


def moe_router(x2d, w_router, dims: MoEDims):
    """Top-k softmax routing over the router's width, with capacity.
    x2d: (N, D) -> dispatch (N, E, C) one-hot and combine (N, E, C)
    weights over the E experts held here; claims on experts held
    elsewhere and overflowed claims drop (standard GShard behaviour)."""
    N = x2d.shape[0]
    E = dims.num_experts
    logits = (x2d.astype(jnp.float32) @ w_router.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                    # (N, R)
    gate_vals, gate_idx = lax.top_k(probs, dims.top_k)         # (N, k)
    local = gate_idx - dims.first      # outside [0, E): held elsewhere
    # position of each (token, slot) within its expert queue
    onehot = jax.nn.one_hot(local, E, dtype=jnp.int32)         # (N, k, E)
    flat = onehot.reshape(N * dims.top_k, E)
    pos_in_expert = (jnp.cumsum(flat, axis=0) - flat) \
        .reshape(N, dims.top_k, E)
    pos = jnp.sum(pos_in_expert * onehot, axis=-1)             # (N, k)
    keep = (local >= 0) & (local < E) & (pos < dims.capacity)
    disp = (jax.nn.one_hot(local, E, dtype=x2d.dtype)
            * keep[..., None].astype(x2d.dtype))               # (N,k,E)
    cap_onehot = jax.nn.one_hot(pos, dims.capacity, dtype=x2d.dtype)
    dispatch = jnp.einsum("nke,nkc->nec", disp, cap_onehot)    # (N,E,C)
    combine = jnp.einsum("nke,nkc,nk->nec", disp, cap_onehot,
                         gate_vals.astype(x2d.dtype))
    return dispatch, combine, _load_balance_loss(probs, gate_idx)


def _load_balance_loss(probs, gate_idx):
    """Switch-style auxiliary load-balancing loss."""
    R = probs.shape[-1]                  # the router's width
    me = jnp.mean(probs, axis=0)
    hits = jax.nn.one_hot(gate_idx[:, 0], R)
    ce = jnp.mean(hits, axis=0)
    return R * jnp.sum(me * ce)


def moe_ffn_dense(x2d, p, dims: MoEDims):
    """Reference dispatch -> per-expert SwiGLU -> combine via (N, E, C)
    one-hot einsums (GShard formulation). O(N*E*C) memory: oracle /
    smoke-scale only — the production path is moe_ffn below."""
    dispatch, combine, aux = moe_router(x2d, p["router"], dims)
    xe = jnp.einsum("nec,nd->ecd", dispatch, x2d)              # (E, C, D)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, p["w_gate"])) \
        * jnp.einsum("ecd,edf->ecf", xe, p["w_up"])
    ye = jnp.einsum("ecf,efd->ecd", h, p["w_down"])            # (E, C, D)
    y = jnp.einsum("nec,ecd->nd", combine, ye)
    return y, aux


def moe_ffn(x2d, p, dims: MoEDims, *, return_keep=False):
    """Group-local sort/scatter dispatch -> grouped SwiGLU -> combine.

    O(N*k*D) memory (no (N, E, C) one-hots). Tokens are dispatched within
    G data-parallel groups (G = data-axis size at trace time, 1 outside a
    mesh): all sort/scatter/gather indices are *local to a group*, so the
    SPMD partitioner runs them per-shard instead of replicating — the
    only cross-group movement is the (G, E, Cg, D) -> (E, G*Cg, D)
    relayout, which lowers to the canonical MoE all-to-all. Capacity is
    per group (C/G), the standard GShard data-parallel drop rule; with
    G == 1 the result is bit-identical to moe_ffn_dense.

    The router scores all of its experts; only the E held here
    (``dims.first`` onwards) are computed, and claims on the others are
    left out — the share of the layer one chip of an expert-parallel
    group gives, without the exchange.

    The (E, C, D) expert batch is the paper's tile pool: one tile per
    expert, executed as a grouped weight-stationary GEMM (kernels.
    packed_mvm on TPU), experts sharded across D_h = the model axis.

    ``return_keep`` appends the realized (N, K) keep mask: claims on held
    experts within capacity.

    At a capacity of N or more (``moe_dims_dropless``) no claim can
    overflow and a dispatch would fill E x N rows anyway, so there is
    none: every held expert runs every token, weighted by its gate where
    the token claimed it and by zero elsewhere.
    """
    N, D = x2d.shape
    E, K, C = dims.num_experts, dims.top_k, dims.capacity
    G = dp_group_count()
    if N % G or C % G:
        G = 1
    n, Cg = N // G, C // G

    logits = x2d.astype(jnp.float32) @ p["router"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                    # (N, R)
    gate_vals, gate_idx = lax.top_k(probs, K)                  # (N, K)
    aux = _load_balance_loss(probs, gate_idx)
    local = gate_idx - dims.first
    held = (local >= 0) & (local < E)

    if C >= N:                                                 # dropless
        gates = jnp.einsum("nke,nk->en", jax.nn.one_hot(local, E),
                           jnp.where(held, gate_vals, 0.0))    # (E, N)
        h = jax.nn.silu(jnp.einsum("nd,edf->enf", x2d, p["w_gate"])) \
            * jnp.einsum("nd,edf->enf", x2d, p["w_up"])
        h = shard_hint(h * gates[..., None].astype(h.dtype), "tp", "dp", None)
        y = jnp.einsum("enf,efd->nd", h, p["w_down"]).astype(x2d.dtype)
        y = shard_hint(y, "dp", None)
        return (y, aux, held) if return_keep else (y, aux)

    # --- group-local dispatch (vmapped over G) --------------------------------
    e_flat = jnp.where(held, local, E).reshape(G, n * K)       # E: elsewhere
    t_flat = jnp.arange(n * K, dtype=jnp.int32) // K           # local rows
    order = jnp.argsort(e_flat, axis=-1, stable=True)          # (G, n*K)
    e_sorted = jnp.take_along_axis(e_flat, order, axis=-1)
    counts = jax.vmap(lambda e: jnp.bincount(e, length=E + 1))(e_flat)
    offsets = jnp.cumsum(counts, axis=-1) - counts             # (G, E+1)
    pos = jnp.arange(n * K, dtype=jnp.int32)[None] \
        - jnp.take_along_axis(offsets, e_sorted, axis=-1)
    keep = (e_sorted < E) & (pos < Cg)
    e_sorted = jnp.where(keep, e_sorted, 0)
    pos_c = jnp.where(keep, pos, Cg)                           # Cg = trash
    xg = shard_hint(x2d.reshape(G, n, D), "dp", None, None)
    x_rep = jnp.take_along_axis(
        xg, t_flat[order][..., None], axis=1)                  # (G, n*K, D)
    x_rep = shard_hint(x_rep, "dp", None, None)

    def scatter_g(e_s, p_c, xr):
        return jnp.zeros((E, Cg + 1, D), x2d.dtype) \
            .at[e_s, p_c].set(xr)[:, :Cg]

    xe_g = jax.vmap(scatter_g)(e_sorted, pos_c, x_rep)         # (G,E,Cg,D)
    xe_g = shard_hint(xe_g, "dp", "tp", None, None)
    # relayout to expert-major: the MoE all-to-all
    xe = xe_g.transpose(1, 0, 2, 3).reshape(E, C, D)
    xe = shard_hint(xe, "tp", "dp", None)                      # EP x token-DP

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, p["w_gate"])) \
        * jnp.einsum("ecd,edf->ecf", xe, p["w_up"])
    h = shard_hint(h, "tp", "dp", None)
    ye = jnp.einsum("ecf,efd->ecd", h, p["w_down"])            # (E, C, D)
    ye = shard_hint(ye, "tp", "dp", None)

    # reverse all-to-all + group-local combine
    ye_g = ye.reshape(E, G, Cg, D).transpose(1, 0, 2, 3)       # (G,E,Cg,D)
    ye_g = shard_hint(ye_g, "dp", "tp", None, None)

    def gather_g(y_e, e_s, p_c):
        pad = jnp.concatenate([y_e, jnp.zeros((E, 1, D), y_e.dtype)],
                              axis=1)
        return pad[e_s, p_c]                                   # (n*K, D)

    y_rep = jax.vmap(gather_g)(ye_g, e_sorted, pos_c)          # (G,n*K,D)
    w = (jnp.take_along_axis(gate_vals.reshape(G, n * K), order, axis=-1)
         * keep.astype(jnp.float32)).astype(x2d.dtype)

    def combine_g(yr, wg, og):
        return jnp.zeros((n, D), x2d.dtype).at[t_flat[og]].add(
            yr * wg[:, None])

    y = jax.vmap(combine_g)(y_rep, w, order)                   # (G, n, D)
    y = shard_hint(y, "dp", None, None)
    if return_keep:
        inv = jnp.argsort(order, axis=-1)
        keep_nk = jnp.take_along_axis(keep, inv, axis=-1).reshape(N, K)
        return y.reshape(N, D), aux, keep_nk
    return y.reshape(N, D), aux


def init_moe_params(key, cfg, d_model):
    m = cfg.moe
    ks = jax.random.split(key, 4)
    E, F = m.num_experts, m.d_ff_expert
    p = {
        "router": dense_init(ks[0], d_model, m.router_width),
        "w_gate": trunc_normal(ks[1], (E, d_model, F),
                               std=1.0 / math.sqrt(d_model)),
        "w_up": trunc_normal(ks[2], (E, d_model, F),
                             std=1.0 / math.sqrt(d_model)),
        "w_down": trunc_normal(ks[3], (E, F, d_model),
                               std=1.0 / math.sqrt(F)),
    }
    if m.num_shared_experts:
        ks2 = jax.random.split(ks[3], 3)
        Fs = F * m.num_shared_experts
        p["shared_gate"] = dense_init(ks2[0], d_model, Fs)
        p["shared_up"] = dense_init(ks2[1], d_model, Fs)
        p["shared_down"] = dense_init(ks2[2], Fs, d_model)
    return p


# --- multi-step decode fusion ----------------------------------------------------

def multi_step_decode(step_fn, hmax: int, state, pending, lengths,
                      remaining, page_table, mask, h, teacher=None):
    """Run up to ``h`` decode steps of ``step_fn`` inside one traced loop.

    ``step_fn(state, tokens, page_table, lengths, active) -> (logits,
    state)`` is a single-token decode body (every paged/recurrent model
    in this package shares that shape). The loop keeps the whole
    token-feedback cycle on device: greedy argmax sampling, the pending-
    token carry, length/remaining advancement and end-of-budget masking
    all happen inside the scanned step, so the host syncs once per
    horizon instead of once per token.

    ``h`` is a traced scalar (one compile serves every horizon length);
    ``hmax`` is the static height of the token out-buffer, so the jit
    cache is keyed on ``hmax`` alone. ``mask`` (B,) bool selects the
    slots this call advances; a slot additionally drops out of the live
    set when its ``remaining`` token budget hits zero (EOS-by-budget —
    the engines clamp ``h`` so this never fires mid-horizon, but the
    kernel stays correct under looser horizons). Inactive slots feed
    token 0 and write to the trash page (row 0), matching the per-step
    engines' conventions exactly.

    ``teacher`` ((hmax, B) int32 or None) forces the fed-back token per
    step instead of the argmax — the teacher-forced replay path.
    Returns ``(tokens (hmax, B) int32, state, pending, lengths,
    remaining)``; rows of ``tokens`` past ``h`` (or past a slot's
    budget) are 0.
    """
    B = pending.shape[0]
    # page 0 is the trash page (kv_pager.TRASH_PAGE): masked-out slots
    # gather/scatter there and attention lengths gate it out
    pt = jnp.where(mask[:, None], page_table, 0)

    def body(i, carry):
        state, pending, lengths, remaining, out = carry
        live = mask & (remaining > 0)
        toks = jnp.where(live, pending, 0)
        lens = jnp.where(live, lengths, 0)
        logits, state = step_fn(state, toks, pt, lens, live)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        if teacher is not None:
            nxt = teacher[i]
        pending = jnp.where(live, nxt, pending)
        out = out.at[i].set(jnp.where(live, nxt, 0))
        took = live.astype(jnp.int32)
        return state, pending, lengths + took, remaining - took, out

    out0 = jnp.zeros((hmax, B), jnp.int32)
    state, pending, lengths, remaining, out = lax.fori_loop(
        0, h, body, (state, pending, lengths, remaining, out0))
    return out, state, pending, lengths, remaining


# --- losses ----------------------------------------------------------------------

def softmax_xent(logits, labels, *, z_loss=1e-4):
    """Cross-entropy with z-loss; logits (..., V) f32, labels (...) int32."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    loss = lse - ll
    if z_loss:
        loss = loss + z_loss * jnp.square(lse)
    return loss
