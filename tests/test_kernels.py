"""Per-kernel allclose validation vs ref.py oracles (interpret=True on CPU).

Sweeps shapes/dtypes per the task spec. bf16 tolerances are loose (the
kernels accumulate in f32 but inputs are quantized to bf16).
"""

import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import (build_block_meta, decode_attention,
                           flash_attention, grouped_mvm,
                           packed_canvas_matmul, ref)
from repro.kernels import ops

# the module; the package re-exports its function under the same name
_dec = importlib.import_module("repro.kernels.decode_attention")

# f32 tol covers blocked-reduction order differences vs one-shot einsum
TOL = {jnp.float32: dict(rtol=1e-4, atol=2e-4),
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


def rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


# --- grouped MVM --------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("E,C,D,F", [
    (4, 128, 128, 128),
    (2, 256, 512, 384),
    (8, 64, 96, 160),     # odd sizes -> block-size fallback path
    (1, 128, 256, 128),
])
def test_grouped_mvm(E, C, D, F, dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    x = rand(k1, (E, C, D), dtype)
    w = rand(k2, (E, D, F), dtype)
    got = grouped_mvm(x, w, interpret=True)
    want = ref.grouped_mvm(x, w)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


# --- packed canvas -------------------------------------------------------------------

def _blocks_case(key, R, C, B, dtype, block_coords):
    """Build a block-sparse virtual plane from (kb, cb) coords."""
    kx, kw = jax.random.split(key)
    x = rand(kx, (B, R), dtype)
    blocks = np.asarray(sorted(set(block_coords)), np.int64)
    meta, order = build_block_meta(blocks)
    wb = rand(kw, (len(blocks), 128, 128), dtype)
    wd = ref.blocks_to_dense(wb, meta, R, C).astype(dtype)
    return x, wb, jnp.asarray(meta), wd


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_packed_canvas_block_sparse(dtype):
    # block-diagonal + a row-sharing column strip + an isolated block
    R, C, B = 512, 640, 128
    coords = [(0, 0), (1, 1), (2, 2), (3, 3),     # diagonal
              (0, 4), (1, 4), (2, 4), (3, 4),     # full column strip
              (2, 0)]                             # extra off-diagonal
    x, wb, meta, wd = _blocks_case(jax.random.PRNGKey(1), R, C, B, dtype,
                                   coords)
    got = packed_canvas_matmul(x, wb, meta, interpret=True)
    want = ref.packed_canvas(x, wd)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


def test_packed_canvas_single_block_runs():
    # every output column block has exactly one k-block (first == last)
    R, C, B = 256, 256, 128
    x, wb, meta, wd = _blocks_case(jax.random.PRNGKey(2), R, C, B,
                                   jnp.float32, [(0, 0), (1, 1)])
    got = packed_canvas_matmul(x, wb, meta, interpret=True)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref.packed_canvas(x, wd)),
                               rtol=1e-4, atol=1e-4)


def test_block_meta_structure():
    blocks = np.array([[1, 0], [3, 0], [0, 1]])
    meta, order = build_block_meta(blocks)
    assert meta.shape == (4, 3)
    # ordered by (cb, kb): (1,0), (3,0), (0,1)
    assert list(meta[0]) == [1, 3, 0]          # kb
    assert list(meta[1]) == [0, 0, 1]          # cb
    assert list(meta[2]) == [1, 0, 1]          # first-of-run
    assert list(meta[3]) == [0, 1, 1]          # last-of-run


# --- flash attention -----------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,T,H,KV,dh,window", [
    (2, 256, 256, 4, 2, 64, 0),        # GQA causal
    (1, 128, 384, 8, 8, 64, 0),        # MHA, suffix-aligned (prefix cache)
    (2, 256, 256, 4, 1, 128, 0),       # MQA
    (1, 256, 256, 2, 2, 64, 128),      # local window (recurrentgemma)
])
def test_flash_attention(B, S, T, H, KV, dh, window, dtype):
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = rand(ks[0], (B, H, S, dh), dtype)
    k = rand(ks[1], (B, KV, T, dh), dtype)
    v = rand(ks[2], (B, KV, T, dh), dtype)
    got = flash_attention(q, k, v, causal=True, window=window,
                          bq=128, bkv=128, interpret=True)
    want = ref.mha_attention(
        jnp.transpose(q, (0, 2, 1, 3)), jnp.transpose(k, (0, 2, 1, 3)),
        jnp.transpose(v, (0, 2, 1, 3)), causal=True, window=window)
    want = jnp.transpose(want, (0, 2, 1, 3))
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


@pytest.mark.parametrize("bq,bkv", [(64, 64), (128, 256), (256, 128)])
def test_flash_attention_block_sweep(bq, bkv):
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    B, S, H, KV, dh = 1, 512, 2, 1, 64
    q = rand(ks[0], (B, H, S, dh), jnp.float32)
    k = rand(ks[1], (B, KV, S, dh), jnp.float32)
    v = rand(ks[2], (B, KV, S, dh), jnp.float32)
    got = flash_attention(q, k, v, bq=bq, bkv=bkv, interpret=True)
    want = jnp.transpose(ref.mha_attention(
        jnp.transpose(q, (0, 2, 1, 3)), jnp.transpose(k, (0, 2, 1, 3)),
        jnp.transpose(v, (0, 2, 1, 3))), (0, 2, 1, 3))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# --- decode attention ----------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,T,H,KV,dh,bt", [
    (4, 512, 8, 2, 64, 256),
    (2, 1024, 4, 4, 128, 256),
    (3, 384, 8, 1, 64, 128),
])
def test_decode_attention(B, T, H, KV, dh, bt, dtype):
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    G = H // KV
    q = rand(ks[0], (B, KV, G, dh), dtype)
    k = rand(ks[1], (B, KV, T, dh), dtype)
    v = rand(ks[2], (B, KV, T, dh), dtype)
    lengths = jax.random.randint(ks[3], (B,), 1, T + 1)
    got = decode_attention(q, k, v, lengths, bt=bt, interpret=True)
    want = ref.decode_attention(
        q.reshape(B, H, dh), jnp.transpose(k, (0, 2, 1, 3)),
        jnp.transpose(v, (0, 2, 1, 3)), lengths)
    np.testing.assert_allclose(np.asarray(got, np.float32).reshape(B, H, dh),
                               np.asarray(want, np.float32), **TOL[dtype])


def test_decode_attention_length_one():
    # only one live cache slot: softmax over a single key
    B, H, KV, T, dh = 2, 4, 2, 256, 64
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    q = rand(ks[0], (B, KV, H // KV, dh), jnp.float32)
    k = rand(ks[1], (B, KV, T, dh), jnp.float32)
    v = rand(ks[2], (B, KV, T, dh), jnp.float32)
    lengths = jnp.ones((B,), jnp.int32)
    got = decode_attention(q, k, v, lengths, bt=128, interpret=True)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(v[:, :, :1, :]
                                          * jnp.ones_like(got)),
                               rtol=1e-5, atol=1e-5)


# --- ops-layer wrappers (model layout round trips) -----------------------------------

def test_ops_attention_model_layout():
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    B, S, H, KV, dh = 2, 192, 4, 2, 64       # S not a block multiple -> pad
    q = rand(ks[0], (B, S, H, dh), jnp.float32)
    k = rand(ks[1], (B, S, KV, dh), jnp.float32)
    v = rand(ks[2], (B, S, KV, dh), jnp.float32)
    got = ops.attention(q, k, v, impl="interpret", bq=64, bkv=64)
    want = ref.mha_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_ops_decode_model_layout():
    ks = jax.random.split(jax.random.PRNGKey(8), 4)
    B, T, H, KV, dh = 2, 320, 8, 2, 64        # T pads to bt multiple
    q = rand(ks[0], (B, H, dh), jnp.float32)
    k = rand(ks[1], (B, T, KV, dh), jnp.float32)
    v = rand(ks[2], (B, T, KV, dh), jnp.float32)
    lengths = jnp.array([T, T // 2], jnp.int32)
    got = ops.decode_attention(q, k, v, lengths, impl="interpret", bt=128)
    want = ref.decode_attention(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_ops_moe_ffn():
    ks = jax.random.split(jax.random.PRNGKey(9), 4)
    E, C, D, F = 4, 128, 64, 128
    xe = rand(ks[0], (E, C, D), jnp.float32)
    wg = rand(ks[1], (E, D, F), jnp.float32)
    wu = rand(ks[2], (E, D, F), jnp.float32)
    wd = rand(ks[3], (E, F, D), jnp.float32)
    got = ops.moe_expert_ffn(xe, wg, wu, wd, impl="interpret")
    want = (jax.nn.silu(ref.grouped_mvm(xe, wg)) * ref.grouped_mvm(xe, wu))
    want = ref.grouped_mvm(want, wd)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


# --- paged decode attention ----------------------------------------------------------

# every paged test reads or writes a stacked pool at a layer that is not 0,
# so a kernel that ignores the layer index reads another layer's pages
LAYERS, LAYER = 3, 1


def _paged_case(key, B, KV, dh, P, page, M, lens, dtype=jnp.float32,
                shuffled=False):
    """k/v pools in kernel layout, stacked over LAYERS layers (LAYERS, KV,
    P, page, dh) + table + lengths; pages owned in id order, or
    (``shuffled``) out of order and non-contiguous."""
    ks = jax.random.split(key, 3)
    kp = rand(ks[0], (LAYERS, KV, P, page, dh), dtype)
    vp = rand(ks[1], (LAYERS, KV, P, page, dh), dtype)
    pt = np.zeros((B, M), np.int32)
    ids = np.arange(1, P)
    free = iter(np.random.default_rng(0).permutation(ids) if shuffled
                else ids)
    for b in range(B):
        for i in range(-(-int(lens[b]) // page)):
            pt[b, i] = next(free)
    return kp, vp, jnp.asarray(pt), jnp.asarray(np.asarray(lens, np.int32))


def _to_model_layout(pages, layer=LAYER):
    """One layer of a stacked pool in the reference's (P, page, KV, dh)."""
    return jnp.transpose(pages[layer], (1, 2, 0, 3))


@pytest.mark.parametrize("B,KV,G,dh,P,page,M,lens", [
    (4, 2, 4, 16, 12, 8, 4, [5, 8, 17, 0]),       # partial/full/multi/empty
    (2, 4, 1, 32, 6, 16, 2, [16, 31]),
    (3, 1, 6, 64, 16, 128, 4, [1, 512, 129]),     # MHA-style big pages
    # block geometry (decode_attention._paged_geometry): page 16 gives
    # ppb = 8 pages per compute block, page 8 gives 16
    pytest.param(2, 2, 2, 32, 30, 16, 16, [200, 77], id="ends-mid-block"),
    pytest.param(2, 2, 2, 32, 20, 16, 12, [128, 144],
                 id="one-block-and-one-page-past"),
    pytest.param(3, 1, 4, 32, 40, 16, 20, [320, 250, 16],
                 id="table-not-multiple-of-block"),
    pytest.param(2, 4, 1, 32, 24, 16, 10, [150, 40],
                 id="mha-several-heads-per-block"),
    pytest.param(2, 16, 1, 128, 12, 16, 4, [40, 17],
                 id="two-head-blocks"),           # kvb = 8 of 16 heads
])
def test_paged_decode_attention_oracle(B, KV, G, dh, P, page, M, lens):
    H = KV * G
    q = rand(jax.random.PRNGKey(0), (B, H, dh), jnp.float32)
    kp, vp, pt, lengths = _paged_case(jax.random.PRNGKey(1), B, KV, dh, P,
                                      page, M, lens)
    got = ops.paged_decode_attention(q, kp, vp, pt, lengths, LAYER,
                                     impl="interpret")
    want = ref.paged_decode_attention(q, _to_model_layout(kp),
                                      _to_model_layout(vp), pt, lengths)
    # acceptance bar: paged kernel matches the jnp oracle to <= 1e-5
    assert np.abs(np.asarray(got) - np.asarray(want)).max() <= 1e-5


@pytest.mark.parametrize("KV,page,dh,M,itemsize,want", [
    (16, 16, 128, 129, 2, (16, 8)),     # olmo-1b.chat: 64 KiB per page DMA
    (4, 16, 128, 129, 2, (4, 8)),       # GQA with 4 KV heads
    (16, 16, 128, 4, 4, (8, 4)),        # float32: two head blocks; M caps ppb
    (1, 128, 256, 4, 4, (1, 1)),        # a head's page over 64 KiB
])
def test_paged_geometry(KV, page, dh, M, itemsize, want):
    """kvb divides KV with a page DMA of at most 64 KiB; ppb gives 128 rows
    per head and block, at least one page and at most the table."""
    assert _dec._paged_geometry(KV, page, dh, M, itemsize) == want


def test_paged_matches_dense_decode_attention():
    """Gathering pages == attending over the contiguous cache."""
    B, KV, G, dh, P, page, M = 2, 2, 2, 32, 9, 8, 4
    H = KV * G
    lens = [19, 26]
    q = rand(jax.random.PRNGKey(2), (B, H, dh), jnp.float32)
    kp, vp, pt, lengths = _paged_case(jax.random.PRNGKey(3), B, KV, dh, P,
                                      page, M, lens)
    k = _to_model_layout(kp)[pt].reshape(B, M * page, KV, dh)
    v = _to_model_layout(vp)[pt].reshape(B, M * page, KV, dh)
    got = ops.paged_decode_attention(q, kp, vp, pt, lengths, LAYER,
                                     impl="interpret")
    want = ref.decode_attention(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("B,KV,G,dh,P,page,M,lens,shuffled", [
    # context lengths exactly at page boundaries (incl. a full table row)
    pytest.param(3, 2, 2, 16, 14, 8, 4, [8, 16, 32], False,
                 id="3-2-2-16-14-8-4-lens0"),
    # single-token contexts (first page barely occupied)
    pytest.param(3, 2, 2, 16, 6, 8, 4, [1, 1, 1], False,
                 id="3-2-2-16-6-8-4-lens1"),
    # all slots dead: no valid keys anywhere, output must be exactly zero
    pytest.param(4, 2, 2, 16, 5, 8, 4, [0, 0, 0, 0], False,
                 id="4-2-2-16-5-8-4-lens2"),
    # non-power-of-two page-table geometry (M=3, P=7) and page size 12
    pytest.param(2, 2, 2, 16, 7, 12, 3, [13, 30], False,
                 id="2-2-2-16-7-12-3-lens3"),
    # mixed: boundary + dead + single in one batch, odd table width
    pytest.param(5, 1, 4, 32, 16, 8, 5, [24, 0, 1, 33, 40], False,
                 id="5-1-4-32-16-8-5-lens4"),
    # page ids owned out of order and non-contiguous, across two blocks
    pytest.param(3, 2, 2, 16, 40, 8, 20, [130, 70, 9], True,
                 id="shuffled-pages"),
    # dead, single-token and full-table slots in one batch (page 8: 16
    # pages per block, so the full table ends 12 columns into its 2nd)
    pytest.param(4, 2, 2, 16, 40, 8, 20, [0, 1, 160, 0], True,
                 id="dead-single-full-table"),
])
def test_paged_decode_attention_edge_shapes(B, KV, G, dh, P, page, M, lens,
                                            shuffled):
    """Differential check at the shapes the engine actually produces:
    page-boundary lengths, single-token contexts, fully dead batches,
    non-power-of-two table geometry and pages owned out of order must all
    match the jnp oracle."""
    H = KV * G
    q = rand(jax.random.PRNGKey(6), (B, H, dh), jnp.float32)
    kp, vp, pt, lengths = _paged_case(jax.random.PRNGKey(7), B, KV, dh, P,
                                      page, M, lens, shuffled=shuffled)
    got = ops.paged_decode_attention(q, kp, vp, pt, lengths, LAYER,
                                     impl="interpret")
    want = ref.paged_decode_attention(q, _to_model_layout(kp),
                                      _to_model_layout(vp), pt, lengths)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() <= 1e-5
    dead = np.asarray(lengths) == 0
    if dead.any():
        assert np.all(np.asarray(got)[dead] == 0.0), \
            "dead slots must produce exactly zero output"


def test_paged_attention_ignores_foreign_pages():
    """No cross-request leakage: trashing every page sequence 0 does NOT
    own must leave sequence 0's output untouched."""
    B, KV, G, dh, P, page, M = 2, 2, 2, 16, 10, 8, 4
    H = KV * G
    q = rand(jax.random.PRNGKey(4), (B, H, dh), jnp.float32)
    kp, vp, pt, lengths = _paged_case(jax.random.PRNGKey(5), B, KV, dh, P,
                                      page, M, [13, 24])
    base = np.asarray(ops.paged_decode_attention(q, kp, vp, pt, lengths,
                                                 LAYER, impl="ref"))
    owned0 = set(np.asarray(pt)[0, :2].tolist())
    foreign = [p for p in range(P) if p not in owned0]
    kp2 = kp.at[:, :, jnp.asarray(foreign)].set(99.0)
    vp2 = vp.at[:, :, jnp.asarray(foreign)].set(-99.0)
    poked = np.asarray(ops.paged_decode_attention(q, kp2, vp2, pt, lengths,
                                                  LAYER, impl="ref"))
    np.testing.assert_array_equal(base[0], poked[0])
    assert np.abs(base[1] - poked[1]).max() > 1.0   # seq 1 did change


# --- in-place append into the paged pools ---------------------------------------

@pytest.mark.parametrize("layer", [0, LAYERS - 1],
                         ids=["first-layer", "last-layer"])
@pytest.mark.parametrize("KV,page,dh,P,page_ids,offsets,dtype", [
    pytest.param(2, 8, 128, 10, [3, 5, 1], [0, 0, 0], jnp.float32,
                 id="offset-0"),
    pytest.param(2, 8, 128, 10, [3, 5, 1], [3, 4, 5], jnp.float32,
                 id="mid-page"),
    pytest.param(2, 8, 128, 10, [3, 5, 1], [7, 7, 7], jnp.bfloat16,
                 id="last-row"),
    # inactive slots all append at row 0 of the trash page
    pytest.param(2, 8, 128, 10, [0, 4, 0, 6, 0], [0, 2, 0, 7, 0],
                 jnp.bfloat16, id="trash-collisions"),
    # 128 KiB pages: 16 slots fit a step's VMEM, so 20 take two steps of 10
    pytest.param(16, 16, 128, 24, list(range(20)), [i % 16 for i in range(20)],
                 jnp.float32, id="two-grid-steps"),
])
def test_paged_kv_append(KV, page, dh, P, page_ids, offsets, dtype, layer):
    """The in-place append matches the XLA scatter bit for bit, outside the
    trash page; there only the collided rows change, each to one of the
    rows written to it; every page not appended to is left as it was."""
    B = len(page_ids)
    ks = jax.random.split(jax.random.PRNGKey(8), 4)
    kp = rand(ks[0], (LAYERS, KV, P, page, dh), dtype)
    vp = rand(ks[1], (LAYERS, KV, P, page, dh), dtype)
    k = rand(ks[2], (B, KV, dh), dtype)
    v = rand(ks[3], (B, KV, dh), dtype)
    pids = jnp.asarray(page_ids, jnp.int32)
    offs = jnp.asarray(offsets, jnp.int32)
    got = ops.paged_kv_append(kp, vp, k, v, layer, pids, offs,
                              impl="interpret")
    want = ops.paged_kv_append(kp, vp, k, v, layer, pids, offs, impl="ref")
    live = np.asarray(page_ids) != 0
    untouched = np.ones((LAYERS, P), bool)
    untouched[layer, np.asarray(page_ids)] = False
    for pool, new, g, w in zip((kp, vp), (k, v), got, want):
        pool, new, g, w = (np.asarray(a) for a in (pool, new, g, w))
        np.testing.assert_array_equal(g[:, :, 1:], w[:, :, 1:])
        np.testing.assert_array_equal(g.transpose(0, 2, 1, 3, 4)[untouched],
                                      pool.transpose(0, 2, 1, 3, 4)[untouched])
        trash = np.asarray(offsets)[~live]
        rows = np.setdiff1d(np.arange(page), trash)
        np.testing.assert_array_equal(g[:, :, 0][:, :, rows],
                                      pool[:, :, 0][:, :, rows])
        for r in np.unique(trash):
            written = new[~live][np.asarray(offsets)[~live] == r]
            assert any(np.array_equal(g[layer, :, 0, r], n) for n in written)


@pytest.mark.parametrize("B,page_bytes,want", [
    (20, 64 * 1024, 20),       # olmo-1b.chat: every slot in one step
    (20, 128 * 1024, 10),      # float32 pages: two steps
    (7, 3 * 2**20, 1),         # a page over the budget: one slot a step
])
def test_append_slots(B, page_bytes, want):
    """Slots per append step: the largest divisor of B whose pages of one
    pool take at most 2 MiB of VMEM."""
    assert _dec._append_slots(B, page_bytes) == want


def test_paged_decode_step_kernels_match_reference(monkeypatch):
    """The dense paged decode step with both paged kernels (interpret
    mode) against the same step on the reference ops: the pools it carries
    through the layer scan, each layer's append and read at its own layer
    index, give the same logits and the same pools."""
    from repro.configs import get_config
    from repro.models import transformer as T

    cfg = get_config("olmo-1b").reduced()
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    B, page, M, P = 3, 8, 4, 12
    st = T.init_paged_decode_state(cfg, P, page)
    st = T.PagedDecodeState(
        *(rand(k, st.k_pages.shape, st.k_pages.dtype)
          for k in jax.random.split(jax.random.PRNGKey(1), 2)))
    pt = jnp.asarray([[1, 2, 3, 0], [4, 5, 0, 0], [0, 0, 0, 0]], jnp.int32)
    lengths = jnp.asarray([17, 8, 0], jnp.int32)
    active = jnp.asarray([True, True, False])
    tokens = jnp.asarray([5, 7, 0], jnp.int32)
    assert cfg.num_layers > 1

    def run(impl):
        with monkeypatch.context() as mp:
            for name in ("paged_kv_append", "paged_decode_attention"):
                mp.setattr(ops, name, functools.partial(getattr(ops, name),
                                                        impl=impl))
            return T.paged_decode_step(cfg, params, st, tokens, pt, lengths,
                                       active)

    (lg, got), (lw, want) = run("interpret"), run("ref")
    np.testing.assert_allclose(np.asarray(lg), np.asarray(lw), **TOL[
        jnp.bfloat16])
    for g, w in ((got.k_pages, want.k_pages), (got.v_pages, want.v_pages)):
        np.testing.assert_array_equal(np.asarray(g)[:, :, 1:],
                                      np.asarray(w)[:, :, 1:])


# --- packed canvas fused epilogue ----------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("activation", ["none", "relu", "silu", "gelu"])
def test_packed_canvas_epilogue(dtype, activation):
    R, C, B = 256, 384, 128
    coords = [(0, 0), (1, 1), (0, 2), (1, 2)]
    x, wb, meta, wd = _blocks_case(jax.random.PRNGKey(11), R, C, B, dtype,
                                   coords)
    ks = jax.random.split(jax.random.PRNGKey(12), 2)
    bias = rand(ks[0], (C,), dtype)
    res = rand(ks[1], (B, C), dtype)
    base = ref.packed_canvas(x, wd).astype(jnp.float32)
    want = _pc_act(activation)(base + bias.astype(jnp.float32)) \
        + res.astype(jnp.float32)
    got = ops.packed_canvas_matmul(x, wb, meta, impl="interpret", bias=bias,
                                   residual=res, activation=activation)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want.astype(dtype), np.float32),
                               **TOL[dtype])


def _pc_act(name):
    from repro.kernels.packed_canvas import ACTIVATIONS
    return ACTIVATIONS[name]


def test_packed_canvas_epilogue_partial():
    """bias-only and residual-only epilogues (others default to identity)."""
    R, C, B = 256, 256, 128
    x, wb, meta, wd = _blocks_case(jax.random.PRNGKey(13), R, C, B,
                                   jnp.float32, [(0, 0), (1, 1), (1, 0)])
    base = np.asarray(ref.packed_canvas(x, wd))
    bias = rand(jax.random.PRNGKey(14), (C,), jnp.float32)
    got_b = ops.packed_canvas_matmul(x, wb, meta, impl="interpret",
                                     bias=bias)
    np.testing.assert_allclose(np.asarray(got_b), base + np.asarray(bias),
                               **TOL[jnp.float32])
    res = rand(jax.random.PRNGKey(15), (B, C), jnp.float32)
    got_r = ops.packed_canvas_matmul(x, wb, meta, impl="interpret",
                                     residual=res)
    np.testing.assert_allclose(np.asarray(got_r), base + np.asarray(res),
                               **TOL[jnp.float32])


def test_build_block_meta_memoized():
    blocks = np.asarray([[0, 0], [1, 0], [1, 1]], np.int64)
    m1, o1 = build_block_meta(blocks)
    m2, o2 = build_block_meta(np.array(blocks))     # distinct array, same key
    assert m1 is m2 and o1 is o2
    # id() fast path: the SAME array skips even the tobytes() hashing;
    # the cache pins a strong ref so a recycled id can never alias
    m3, o3 = build_block_meta(blocks)
    assert m3 is m1 and o3 is o1
    from repro.kernels.packed_canvas import _META_ID_CACHE
    kept, out = _META_ID_CACHE[id(blocks)]
    assert kept is blocks and out == (m1, o1)
