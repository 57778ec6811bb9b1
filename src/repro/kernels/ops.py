"""Public jit'd wrappers around the Pallas kernels.

Each op accepts the model-layer layout, converts to the kernel layout, and
dispatches to the Pallas kernel on TPU (or with ``interpret=True``) and to
the pure-jnp oracle otherwise — so the model zoo can call these ops
unconditionally and stay runnable on the CPU container.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import decode_attention as _dec
from . import dequant as _dq
from . import flash_attention as _fa
from . import packed_canvas as _pc
from . import packed_mvm as _pm
from . import ref


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


# --- attention -------------------------------------------------------------------

def attention(q, k, v, *, causal=True, window=0, scale=None,
              impl: str = "auto", bq=128, bkv=128):
    """GQA attention in model layout: q (B,S,H,dh), k/v (B,T,KV,dh)."""
    if impl == "ref" or (impl == "auto" and not _on_tpu()):
        return ref.mha_attention(q, k, v, causal=causal, window=window,
                                 scale=scale)
    interpret = impl == "interpret"
    qt = jnp.transpose(q, (0, 2, 1, 3))            # (B, H, S, dh)
    kt = jnp.transpose(k, (0, 2, 1, 3))            # (B, KV, T, dh)
    vt = jnp.transpose(v, (0, 2, 1, 3))
    S, T = qt.shape[2], kt.shape[2]
    bq, bkv = min(bq, S), min(bkv, T)
    qt = _pad_to(qt, 2, bq)
    kt = _pad_to(kt, 2, bkv)
    vt = _pad_to(vt, 2, bkv)
    # padded key slots must stay invisible: causal masking handles suffix
    # padding of keys only if queries are suffix-aligned — recompute offset
    # on the *unpadded* T by masking via window/causal in-kernel using the
    # padded sizes; simplest correct route: pad q too and slice the result.
    out = _fa.flash_attention(qt, kt, vt, causal=causal, window=window,
                              scale=scale, bq=bq, bkv=bkv,
                              interpret=interpret)
    out = out[:, :, :S]
    return jnp.transpose(out, (0, 2, 1, 3))


def decode_attention(q, k, v, lengths, *, scale=None, impl: str = "auto",
                     bt=256):
    """Decode attention in model layout: q (B,H,dh), k/v (B,T,KV,dh)."""
    if impl == "ref" or (impl == "auto" and not _on_tpu()):
        return ref.decode_attention(q, k, v, lengths, scale=scale)
    interpret = impl == "interpret"
    B, H, dh = q.shape
    KV = k.shape[2]
    qt = q.reshape(B, KV, H // KV, dh)
    kt = jnp.transpose(k, (0, 2, 1, 3))            # (B, KV, T, dh)
    vt = jnp.transpose(v, (0, 2, 1, 3))
    bt_eff = min(bt, kt.shape[2])
    kt = _pad_to(kt, 2, bt_eff)
    vt = _pad_to(vt, 2, bt_eff)
    out = _dec.decode_attention(qt, kt, vt, lengths, scale=scale, bt=bt_eff,
                                interpret=interpret)
    return out.reshape(B, H, dh)


def paged_decode_attention(q, k_pages, v_pages, page_table, lengths, *,
                           scale=None, impl: str = "auto"):
    """Paged decode attention: q (B, H, dh) model layout; k/v_pages
    (KV, P, page, dh) *kernel* layout (models.layers.paged_cache_init
    stores pools head-major precisely so the decode hot loop pays no
    pool-wide relayout here); page_table (B, M) int32; lengths (B,).
    On TPU the kernel needs dh a multiple of 128 (it copies pages out of
    HBM, and Mosaic cannot slice a row narrower than a lane tile there);
    narrower heads, which no served configuration has, take the reference."""
    B, H, dh = q.shape
    KV = k_pages.shape[0]
    if impl == "ref" or (impl == "auto" and (not _on_tpu() or dh % 128)):
        kt = jnp.transpose(k_pages, (1, 2, 0, 3))  # (P, page, KV, dh)
        vt = jnp.transpose(v_pages, (1, 2, 0, 3))
        return ref.paged_decode_attention(q, kt, vt, page_table, lengths,
                                          scale=scale)
    interpret = impl == "interpret"
    qt = q.reshape(B, KV, H // KV, dh)
    out = _dec.paged_decode_attention(qt, k_pages, v_pages, page_table,
                                      lengths, scale=scale,
                                      interpret=interpret)
    return out.reshape(B, H, dh)


# --- grouped MoE GEMM --------------------------------------------------------------

def grouped_mvm(x, w, *, impl: str = "auto"):
    """x (E,C,D) @ w (E,D,F) -> (E,C,F)."""
    if impl == "ref" or (impl == "auto" and not _on_tpu()):
        return ref.grouped_mvm(x, w)
    return _pm.grouped_mvm(x, w, interpret=(impl == "interpret"))


def moe_expert_ffn(xe, w_gate, w_up, w_down, *, impl: str = "auto"):
    """SwiGLU over dispatched expert inputs xe (E, C, D)."""
    h = jax.nn.silu(grouped_mvm(xe, w_gate, impl=impl)) \
        * grouped_mvm(xe, w_up, impl=impl)
    return grouped_mvm(h, w_down, impl=impl)


# --- packed canvas -------------------------------------------------------------------

def packed_canvas_matmul(x_packed, w_blocks, meta, *, impl: str = "auto",
                         bb=128, bias=None, residual=None, activation=None):
    """Block-compacted multi-layer MVM; meta from build_block_meta.

    The ref path reconstructs the dense virtual plane — only viable for
    small planes; the kernel path touches just the stored blocks. The
    optional epilogue ``y = act(y + bias) + residual`` is fused into the
    kernel's flush (one HBM write per output block in the decode loop).
    """
    if impl == "ref" or (impl == "auto" and not _on_tpu()):
        import numpy as np
        C = (int(np.asarray(meta)[_pc.META_CB].max()) + 1) * _pc.BLK
        wd = ref.blocks_to_dense(w_blocks, meta, x_packed.shape[1], C)
        y = ref.packed_canvas(x_packed, wd.astype(x_packed.dtype))
        if bias is not None or residual is not None or activation is not None:
            yf = y.astype(jnp.float32)
            if bias is not None:
                yf = yf + bias.astype(jnp.float32)
            yf = _pc.ACTIVATIONS[activation or "none"](yf)
            if residual is not None:
                yf = yf + residual.astype(jnp.float32)
            y = yf.astype(y.dtype)
        return y
    bb = min(bb, x_packed.shape[0])
    return _pc.packed_canvas_matmul(x_packed, w_blocks, meta, bb=bb,
                                    interpret=(impl == "interpret"),
                                    bias=bias, residual=residual,
                                    activation=activation)


def packed_canvas_matmul_dq(x_packed, wq_blocks, scales, meta, *,
                            precision: str, impl: str = "auto", bb=128,
                            bias=None, residual=None, activation=None):
    """Packed-canvas MVM over quantized blocks (compressed weight
    streaming): int8/int4 payload + per-channel scales from
    ``dequant.quantize_blocks``, dequantized inside the block loop.

    The ref path dequantizes via the jnp oracle and reuses the fp ref —
    bit-identical semantics to the kernel's in-loop dequant, which is
    exactly what the golden differentials pin.
    """
    if impl == "ref" or (impl == "auto" and not _on_tpu()):
        w_blocks = _dq.dequantize_blocks(wq_blocks, scales, precision)
        return packed_canvas_matmul(
            x_packed, w_blocks.astype(x_packed.dtype), meta, impl="ref",
            bb=bb, bias=bias, residual=residual, activation=activation)
    bb = min(bb, x_packed.shape[0])
    return _dq.packed_canvas_matmul_dq(
        x_packed, wq_blocks, scales, meta, precision=precision, bb=bb,
        interpret=(impl == "interpret"), bias=bias, residual=residual,
        activation=activation)


build_block_meta = _pc.build_block_meta
