"""Pallas TPU kernels for the perf-critical compute layers.

    packed_canvas     multi-layer block-packed MVM (column-generation output)
    packed_mvm        grouped MoE expert GEMM
    flash_attention   causal/windowed GQA flash attention (train/prefill)
    decode_attention  KV-cache GQA decode attention (dense + paged variants)
                      and the paged pools' in-place append
    dequant           packed-canvas MVM over quantized blocks (int8/int4
                      payload + per-channel scales, dequant fused in-loop)

``ops`` holds the public wrappers (auto CPU-oracle fallback); ``ref`` the
pure-jnp semantics the kernels are validated against (interpret=True).
"""

from . import ops, ref
from .decode_attention import (decode_attention, paged_decode_attention,
                               paged_kv_append)
from .dequant import (dequantize_blocks, fake_quant, packed_canvas_matmul_dq,
                      quantize_blocks)
from .flash_attention import flash_attention
from .packed_canvas import build_block_meta, packed_canvas_matmul
from .packed_mvm import grouped_mvm

__all__ = ["ops", "ref", "flash_attention", "decode_attention",
           "paged_decode_attention", "paged_kv_append", "grouped_mvm",
           "packed_canvas_matmul", "build_block_meta", "quantize_blocks",
           "dequantize_blocks", "packed_canvas_matmul_dq", "fake_quant"]
