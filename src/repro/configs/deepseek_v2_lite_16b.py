"""deepseek-v2-lite-16b [moe] — arXiv:2405.04434, as its published
config.json states it (huggingface.co/deepseek-ai/DeepSeek-V2-Lite).

27L d_model=2048 16H vocab=102400 untied; MLA attention (kv_lora_rank=512,
no q_lora, nope head 128, rope head 64, v head 128) with YaRN rope (factor
40 over 4096, beta 32/1, mscale = mscale_all_dim = 0.707); layer 0 has a
dense SwiGLU of 10944, layers 1-26 an MoE FFN of 2 shared + 64 routed
experts of 1408, softmax router, greedy top-6, no top-k renormalisation;
RMSNorm eps 1e-6.
"""

from .base import MLAConfig, ModelConfig, MoEConfig, RopeScaling

CONFIG = ModelConfig(
    name="deepseek-v2-lite-16b", family="moe",
    num_layers=27, d_model=2048, num_heads=16, num_kv_heads=16,
    d_ff=10944, vocab_size=102400, head_dim=128,
    norm="rmsnorm", first_k_dense=1,
    rope_scaling=RopeScaling(factor=40, original_max_position_embeddings=4096,
                             beta_fast=32, beta_slow=1, mscale=0.707,
                             mscale_all_dim=0.707),
    moe=MoEConfig(num_experts=64, top_k=6, d_ff_expert=1408,
                  num_shared_experts=2),
    mla=MLAConfig(kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128),
)
