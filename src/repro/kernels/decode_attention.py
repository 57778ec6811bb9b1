"""KV-cache GQA decode attention (one query token per sequence), Pallas TPU.

The decode hot loop is memory-bound: the whole KV cache is streamed once
per step while the query is tiny. The kernel tiles the cache time axis and
keeps an online softmax per (batch, kv-head); cache blocks wholly beyond
the live length (scalar-prefetched per batch row) are skipped — both the
DMA-issue cost and the FLOPs scale with the *live* cache, which is the
decode analogue of skipping unoccupied canvas blocks.

Layouts (arranged by ops.py):
    q: (B, KV, G, dh)     k, v: (B, KV, T, dh)     lengths: (B,) int32
Grid: (B, KV, T/bt).

``paged_decode_attention`` is the same online softmax over a *paged* cache:
k/v live in a shared page pool (KV, P, page, dh) and each sequence names
its pages through an int32 page table (B, M). Both the table and the live
lengths are scalar-prefetched. Grid: (B, KV // kvb), one step per slot and
block of kvb heads. The pools stay in HBM: the step loops over the slot's
live pages only, ``ppb`` pages per compute block, copying each page of its
kvb heads with one DMA into a double-buffered VMEM block. Dead table
columns cost no grid step and no DMA, so the cache bytes moved scale with
the pages a sequence owns. kvb is the largest head block whose page is one
DMA of at most 64 KiB, ppb gives each head 128 rows per block (16 and 8 at
olmo-1b).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, bt: int, g: int):
    b, tk = pl.program_id(0), pl.program_id(2)
    length = len_ref[b]

    @pl.when(tk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(tk * bt < length)                      # skip dead cache blocks
    def _step():
        qb = q_ref[0, 0].astype(jnp.float32) * scale      # (G, dh)
        kb = k_ref[0, 0].astype(jnp.float32)              # (bt, dh)
        logits = jax.lax.dot_general(
            qb, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)           # (G, bt)
        t_pos = tk * bt + jax.lax.broadcasted_iota(jnp.int32, (g, bt), 1)
        mask = t_pos < length
        logits = jnp.where(mask, logits, NEG_INF)

        m_prev = m_ref[...]                               # (G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(logits, -1, keepdims=True))
        p = jnp.where(mask, jnp.exp(logits - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, -1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
            p, v_ref[0, 0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(tk == pl.num_programs(2) - 1)
    def _flush():
        l = l_ref[...]
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("scale", "bt", "interpret"))
def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     lengths: jax.Array, *, scale: float | None = None,
                     bt: int = 256, interpret: bool = False) -> jax.Array:
    """q: (B, KV, G, dh); k/v: (B, KV, T, dh); lengths (B,) -> (B, KV, G, dh)."""
    B, KV, G, dh = q.shape
    T = k.shape[2]
    bt = min(bt, T)
    assert T % bt == 0
    scale = scale if scale is not None else 1.0 / math.sqrt(dh)

    kernel = functools.partial(_kernel, scale=scale, bt=bt, g=G)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, KV, T // bt),
            in_specs=[
                pl.BlockSpec((1, 1, G, dh), lambda b, h, t, L: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, bt, dh), lambda b, h, t, L: (b, h, t, 0)),
                pl.BlockSpec((1, 1, bt, dh), lambda b, h, t, L: (b, h, t, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, G, dh),
                                   lambda b, h, t, L: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, dh), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(lengths.astype(jnp.int32), q, k, v)


# --- paged variant -------------------------------------------------------------

_DMA_BYTES = 64 * 1024      # most bytes of one page across a head block
_BLOCK_ROWS = 128           # cache rows per head in one compute block


def _paged_geometry(KV: int, page: int, dh: int, M: int,
                    itemsize: int) -> tuple[int, int]:
    """(kvb, ppb): heads per grid step and pages per compute block.

    ``kvb`` is the largest divisor of KV whose page (kvb chunks of
    page x dh) is one DMA of at most ``_DMA_BYTES``; ``ppb`` gives each head
    ``_BLOCK_ROWS`` cache rows per block (at least one page, at most the
    table). The double-buffered K and V blocks then take at most
    4 x ppb x kvb x page x dh x itemsize bytes of VMEM: 2 MiB at olmo-1b
    (KV 16, dh 128, page 16, bf16: kvb 16, ppb 8, 64 KiB per DMA).
    """
    head_page = page * dh * itemsize
    kvb = max((d for d in range(1, KV + 1)
               if KV % d == 0 and d * head_page <= _DMA_BYTES), default=1)
    ppb = max(1, min(M, _BLOCK_ROWS // page))
    return kvb, ppb


def _paged_kernel(len_ref, pt_ref, q_ref, k_hbm, v_hbm, o_ref,
                  k_buf, v_buf, sems, m_ref, l_ref, acc_ref, *,
                  scale: float, page: int, ppb: int, kvb: int):
    b, h0 = pl.program_id(0), pl.program_id(1) * kvb
    length = len_ref[b]
    M = pt_ref.shape[1]
    n_pages = jnp.minimum((length + page - 1) // page, M)
    n_blocks = (n_pages + ppb - 1) // ppb
    rows = ppb * page

    def dma(i, slot, action):
        """Start or wait for block i's copies into buffer ``slot``: one DMA
        per live page and pool, moving that page of heads h0..h0+kvb."""
        for j in range(ppb):
            col = i * ppb + j
            pid = pt_ref[b, jnp.minimum(col, M - 1)]   # read even if dead

            @pl.when(col < n_pages)
            def _copy():
                for n, (src, buf) in enumerate(((k_hbm, k_buf),
                                                (v_hbm, v_buf))):
                    cp = pltpu.make_async_copy(src.at[pl.ds(h0, kvb), pid],
                                               buf.at[slot, j],
                                               sems.at[n, slot])
                    getattr(cp, action)()

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(n_blocks > 0)
    def _prefetch():
        dma(0, 0, "start")

    def block(i, carry):
        slot = i % 2

        @pl.when(i + 1 < n_blocks)
        def _next():
            dma(i + 1, 1 - slot, "start")

        dma(i, slot, "wait")
        t0 = i * rows
        pos = t0 + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
        row_live = pos < length     # past it: pages not copied, stale VMEM
        for h in range(kvb):
            qb = q_ref[0, h].astype(jnp.float32) * scale          # (G, dh)
            kb = k_buf[slot, :, h].astype(jnp.float32).reshape(rows, -1)
            vb = v_buf[slot, :, h].astype(jnp.float32).reshape(rows, -1)
            vb = jnp.where(row_live, vb, 0.0)                    # (rows, dh)
            logits = jax.lax.dot_general(
                qb, kb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)               # (G, rows)
            t_pos = t0 + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
            mask = t_pos < length
            logits = jnp.where(mask, logits, NEG_INF)

            m_prev = m_ref[h]                                     # (G, 1)
            m_new = jnp.maximum(m_prev, jnp.max(logits, -1, keepdims=True))
            pr = jnp.where(mask, jnp.exp(logits - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[h] = alpha * l_ref[h] + jnp.sum(pr, -1, keepdims=True)
            acc_ref[h] = alpha * acc_ref[h] + jax.lax.dot_general(
                pr, vb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[h] = m_new
        return carry

    jax.lax.fori_loop(0, n_blocks, block, 0)
    l = l_ref[...]
    o_ref[0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_decode_attention(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, page_table: jax.Array,
                           lengths: jax.Array, *, scale: float | None = None,
                           interpret: bool = False) -> jax.Array:
    """q: (B, KV, G, dh); k/v_pages: (KV, P, page, dh);
    page_table: (B, M) int32 page ids; lengths: (B,) live tokens.

    Sequence b's cache position t lives in page ``page_table[b, t // page]``
    at row ``t % page``. Table entries at or beyond the live length are
    never read (they must still be valid indices — the pager points them
    at its reserved trash page). Returns (B, KV, G, dh); a slot of length 0
    gives zeros. Compiled for TPU, dh must be a multiple of 128.

    Grid (B, KV // kvb): one step per slot and block of kvb heads. The
    pools stay in HBM; the step loops over its slot's live pages only,
    ``ppb`` pages per compute block, each page of the kvb heads one DMA
    into a double-buffered VMEM block, the next block's DMAs in flight
    while one is computed. ``kvb`` and ``ppb`` follow from the shapes
    (``_paged_geometry``).
    """
    B, KV, G, dh = q.shape
    _, P, page, _ = k_pages.shape
    M = page_table.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(dh)
    kvb, ppb = _paged_geometry(KV, page, dh, M, k_pages.dtype.itemsize)

    kernel = functools.partial(_paged_kernel, scale=scale, page=page,
                               ppb=ppb, kvb=kvb)
    qo_spec = pl.BlockSpec((1, kvb, G, dh), lambda b, h, L, pt: (b, h, 0, 0))
    buf = pltpu.VMEM((2, ppb, kvb, page, dh), k_pages.dtype)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, KV // kvb),
            in_specs=[qo_spec, pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=qo_spec,
            scratch_shapes=[
                buf, buf, pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((kvb, G, 1), jnp.float32),
                pltpu.VMEM((kvb, G, 1), jnp.float32),
                pltpu.VMEM((kvb, G, dh), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(lengths.astype(jnp.int32), page_table.astype(jnp.int32), q, k_pages,
      v_pages)
