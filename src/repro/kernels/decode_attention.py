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
k/v live in a shared page pool, stacked over layers (L, KV, P, page, dh),
and each sequence names its pages through an int32 page table (B, M). The
table, the live lengths and the layer index are scalar-prefetched. Grid:
(B, KV // kvb), one step per slot and block of kvb heads. The pools stay in
HBM: the step loops over the slot's live pages of its layer only, ``ppb``
pages per compute block, copying each page of its kvb heads with one DMA
into a double-buffered VMEM block. Dead table columns cost no grid step and
no DMA, so the cache bytes moved scale with the pages a sequence owns. kvb
is the largest head block whose page is one DMA of at most 64 KiB, ppb
gives each head 128 rows per block (16 and 8 at olmo-1b).

``paged_kv_append`` writes each slot's new K and V row into its page of the
same stacked pools, in place (the pools are aliased input to output and
stay in HBM): it reads the slot's whole page of all heads, patches the one
row and writes the page back, every slot's copies in flight together. A
single row is narrower than the pool's tiling, so a page is the smallest
copy. Kept in the kernel's layout and never sliced per layer, the pools
cross a decode step without a copy.
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


def _paged_kernel(len_ref, pt_ref, layer_ref, q_ref, k_hbm, v_hbm, o_ref,
                  k_buf, v_buf, sems, m_ref, l_ref, acc_ref, *,
                  scale: float, page: int, ppb: int, kvb: int):
    b, h0 = pl.program_id(0), pl.program_id(1) * kvb
    length, layer = len_ref[b], layer_ref[0]
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
                    cp = pltpu.make_async_copy(
                        src.at[layer, pl.ds(h0, kvb), pid], buf.at[slot, j],
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
                           lengths: jax.Array, layer: jax.Array, *,
                           scale: float | None = None,
                           interpret: bool = False) -> jax.Array:
    """q: (B, KV, G, dh); k/v_pages: (L, KV, P, page, dh), every layer's
    pool; page_table: (B, M) int32 page ids; lengths: (B,) live tokens;
    layer: scalar int32, the pool layer read.

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
    page = k_pages.shape[3]
    M = page_table.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(dh)
    kvb, ppb = _paged_geometry(KV, page, dh, M, k_pages.dtype.itemsize)

    kernel = functools.partial(_paged_kernel, scale=scale, page=page,
                               ppb=ppb, kvb=kvb)
    qo_spec = pl.BlockSpec((1, kvb, G, dh), lambda b, h, *_: (b, h, 0, 0))
    buf = pltpu.VMEM((2, ppb, kvb, page, dh), k_pages.dtype)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
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
    )(lengths.astype(jnp.int32), page_table.astype(jnp.int32),
      jnp.reshape(layer, (1,)).astype(jnp.int32), q, k_pages, v_pages)


# --- in-place append into the paged pools ----------------------------------------

_APPEND_BYTES = 2 * 2**20   # most bytes of one pool's pages a grid step holds


def _append_slots(B: int, page_bytes: int) -> int:
    """Slots per grid step of ``paged_kv_append``: the largest divisor of B
    whose pages of one pool take at most ``_APPEND_BYTES`` of VMEM (all 20
    slots of olmo-1b.chat, 64 KiB a page, in one step)."""
    return max((d for d in range(1, B + 1)
                if B % d == 0 and d * page_bytes <= _APPEND_BYTES), default=1)


def _append_kernel(layer_ref, pid_ref, off_ref, k_new, v_new, k_in, v_in,
                   k_out, v_out, k_buf, v_buf, sems):
    """Grid step i appends for slots i*sb .. i*sb + sb - 1: read each one's
    page (all heads) into VMEM, patch its row, write the page back. All
    reads are waited for before any write starts, so slots that share the
    trash page each write back its untouched rows as they were."""
    sb = k_buf.shape[0]
    base = pl.program_id(0) * sb
    layer = layer_ref[0]
    pools = ((k_in, k_out, k_buf), (v_in, v_out, v_buf))

    def each(action, write):
        def body(s, carry):
            pid = pid_ref[base + s]
            for n, (src, dst, buf) in enumerate(pools):
                ends = ((buf.at[s], dst.at[layer, :, pid]) if write
                        else (src.at[layer, :, pid], buf.at[s]))
                getattr(pltpu.make_async_copy(*ends, sems.at[n]), action)()
            return carry
        jax.lax.fori_loop(0, sb, body, 0)

    def patch(s, carry):
        rows = jax.lax.broadcasted_iota(jnp.int32, k_buf.shape[1:], 1)
        hit = rows == off_ref[base + s]
        for new, buf in ((k_new, k_buf), (v_new, v_buf)):
            buf[s] = jnp.where(hit, new[s], buf[s])       # (KV, 1, dh) row
        return carry

    each("start", False)
    each("wait", False)
    jax.lax.fori_loop(0, sb, patch, 0)
    each("start", True)
    each("wait", True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_kv_append(k_pages: jax.Array, v_pages: jax.Array, k: jax.Array,
                    v: jax.Array, layer: jax.Array, page_ids: jax.Array,
                    offsets: jax.Array, *, interpret: bool = False
                    ) -> tuple[jax.Array, jax.Array]:
    """Write k[b], v[b] (B, KV, dh) into row ``offsets[b]`` of page
    ``page_ids[b]`` of pool layer ``layer``; k/v_pages (L, KV, P, page, dh)
    are updated in place and returned. Slots that do not append point at
    the trash page (their writes collide harmlessly there); the pages that
    live slots append to must be distinct, as copy-on-write makes them.

    Grid (B // sb,): sb slots a step (``_append_slots``), each one's page
    of all heads read into VMEM, its row patched and the page written
    back, the step's copies in flight together.
    """
    B, KV, dh = k.shape
    page = k_pages.shape[3]
    sb = _append_slots(B, KV * page * dh * k_pages.dtype.itemsize)
    new_spec = pl.BlockSpec((sb, KV, 1, dh), lambda i, *_: (i, 0, 0, 0))
    pool_spec = pl.BlockSpec(memory_space=pl.ANY)
    buf = pltpu.VMEM((sb, KV, page, dh), k_pages.dtype)
    return pl.pallas_call(
        _append_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B // sb,),
            in_specs=[new_spec, new_spec, pool_spec, pool_spec],
            out_specs=[pool_spec, pool_spec],
            scratch_shapes=[buf, buf, pltpu.SemaphoreType.DMA((2,))],
        ),
        out_shape=[jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
                   jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype)],
        input_output_aliases={5: 0, 6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), page_ids.astype(jnp.int32),
      offsets.astype(jnp.int32), k[:, :, None].astype(k_pages.dtype),
      v[:, :, None].astype(v_pages.dtype), k_pages, v_pages)
