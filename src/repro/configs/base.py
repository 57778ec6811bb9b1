"""Config system: one ModelConfig per assigned architecture.

Every architecture in the assignment pool is a selectable config
(``--arch <id>``). ``reduced()`` yields a tiny same-family config for CPU
smoke tests; the full configs are exercised only through the dry-run
(ShapeDtypeStruct lowering, no allocation).
"""

from __future__ import annotations

import dataclasses
import math
from collections.abc import Mapping
from typing import Literal

Family = Literal["dense", "moe", "ssm", "hybrid", "encdec", "vlm"]


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    """Routed experts of one MoE layer. ``num_experts`` is how many this
    chip holds, ids ``0 .. num_experts - 1`` of the router's
    ``router_experts`` (0: the router's width is ``num_experts``, every
    expert is held). Claims on experts held elsewhere are left out."""
    num_experts: int
    top_k: int
    d_ff_expert: int
    num_shared_experts: int = 0
    capacity_factor: float = 1.25
    router_experts: int = 0

    @property
    def router_width(self) -> int:
        return self.router_experts or self.num_experts


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    """DeepSeek-V2 multi-head latent attention."""
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128


@dataclasses.dataclass(frozen=True)
class RopeScaling:
    """YaRN rope scaling, with the keys of DeepSeek-V2's ``rope_scaling``."""
    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0
    type: str = "yarn"

    def __post_init__(self):
        if self.type != "yarn":
            raise ValueError(f"rope scaling {self.type!r}: only YaRN runs")


@dataclasses.dataclass(frozen=True)
class RecurrentConfig:
    """RG-LRU / RWKV-style recurrence parameters."""
    lru_width: int = 0             # 0 -> d_model
    conv_width: int = 4
    window: int = 2048             # local-attention window (hybrid archs)
    block_pattern: tuple[str, ...] = ("rec", "rec", "attn")  # griffin 2:1


@dataclasses.dataclass(frozen=True)
class EncoderConfig:
    """Encoder tower for enc-dec (whisper) / vision stub (vlm)."""
    num_layers: int = 4
    seq_len: int = 1500            # precomputed frame/patch embeddings (stub)


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: Family
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0              # 0 -> d_model // num_heads
    norm: Literal["rmsnorm", "layernorm", "nonparametric"] = "rmsnorm"
    use_bias: bool = False
    qkv_bias: bool = False
    tie_embeddings: bool = False
    rope_theta: float = 10000.0
    mrope: bool = False            # Qwen2-VL multimodal 3-D RoPE
    rope_scaling: RopeScaling | None = None
    first_k_dense: int = 0         # leading layers with a dense FFN of d_ff
    moe: MoEConfig | None = None
    mla: MLAConfig | None = None
    recurrent: RecurrentConfig | None = None
    encoder: EncoderConfig | None = None

    def __post_init__(self):
        # nested groups may come as mappings (a JSON configuration file)
        for name, kind in (("rope_scaling", RopeScaling), ("moe", MoEConfig),
                           ("mla", MLAConfig), ("recurrent", RecurrentConfig),
                           ("encoder", EncoderConfig)):
            v = getattr(self, name)
            if isinstance(v, Mapping):
                v = dict(v)
                if "block_pattern" in v:
                    v["block_pattern"] = tuple(v["block_pattern"])
                object.__setattr__(self, name, kind(**v))
        if not 0 <= self.first_k_dense <= self.num_layers:
            raise ValueError(f"first_k_dense {self.first_k_dense} of "
                             f"{self.num_layers} layers")
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim",
                               self.d_model // self.num_heads)
        assert self.num_heads % max(self.num_kv_heads, 1) == 0 or \
            self.mla is not None

    # -- derived sizes ---------------------------------------------------------

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim

    def param_count(self) -> int:
        """Analytic parameter count (embeddings + blocks + head)."""
        D, F, V, L = self.d_model, self.d_ff, self.vocab_size, self.num_layers
        embed = V * D * (1 if self.tie_embeddings else 2)
        if self.family == "ssm":           # rwkv6
            att = D * D * 4 + D * 64 * 10   # r,k,v,o + lora mixers (approx)
            ffn = D * F + F * D
        elif self.mla is not None:
            m = self.mla
            att = (D * self.num_heads * (m.qk_nope_head_dim + m.qk_rope_head_dim)
                   + D * (m.kv_lora_rank + m.qk_rope_head_dim)
                   + m.kv_lora_rank * self.num_heads
                   * (m.qk_nope_head_dim + m.v_head_dim)
                   + self.num_heads * m.v_head_dim * D)
            ffn = 0  # counted via moe below
        else:
            att = D * self.q_dim + 2 * D * self.kv_dim + self.q_dim * D
            ffn = 3 * D * F
        if self.moe:
            fe = self.moe.d_ff_expert
            ffn = (self.moe.num_experts + self.moe.num_shared_experts) \
                * 3 * D * fe + D * self.moe.router_width
        if self.family == "hybrid" and self.recurrent:
            W = self.recurrent.lru_width or D
            rec = D * W * 2 + W * D + W * self.recurrent.conv_width + 2 * W
            natt = sum(1 for i in range(L)
                       if self.recurrent.block_pattern[
                           i % len(self.recurrent.block_pattern)] == "attn")
            att = att * natt / L + rec * (1 - natt / L)  # averaged per block
        blocks = L * (att + ffn + 2 * D)
        if self.first_k_dense:      # leading dense layers: SwiGLU of d_ff
            blocks += self.first_k_dense * (3 * D * F - ffn)
        if self.encoder and self.family == "encdec":
            enc = self.encoder.num_layers * (4 * D * D + 2 * D * F + 2 * D)
            blocks += enc + L * (4 * D * D)  # cross-attention
        return int(embed + blocks + D)

    def active_param_count(self) -> int:
        """Parameters touched per token (MoE: only routed top-k + shared)."""
        if not self.moe:
            return self.param_count()
        D, L = self.d_model, self.num_layers - self.first_k_dense
        fe = self.moe.d_ff_expert
        total = self.param_count()
        all_experts = L * self.moe.num_experts * 3 * D * fe
        active = L * (self.moe.top_k + self.moe.num_shared_experts) * 3 * D * fe
        return int(total - all_experts + active)

    def reduced(self) -> "ModelConfig":
        """Tiny same-family config for CPU smoke tests.

        Hybrid archs keep one full (rec, rec, attn) pattern period so the
        windowed-attention path (and its serving cache) is exercised —
        two layers would reduce to a pure-recurrence stack. MoE archs
        keep one leading dense layer if they have any (then one MoE
        layer follows it), YaRN if they use it, a shared expert if they
        have one, and hold half of a router of 8 experts, so the expert
        share is exercised."""
        kw: dict = dict(
            name=self.name + "-smoke", family=self.family,
            num_layers=3 if self.family == "hybrid" else 2,
            first_k_dense=min(self.first_k_dense, 1),
            rope_scaling=self.rope_scaling, d_model=64,
            num_heads=4, num_kv_heads=max(1, min(self.num_kv_heads, 2)),
            head_dim=16, d_ff=128, vocab_size=256, norm=self.norm,
            use_bias=self.use_bias, qkv_bias=self.qkv_bias,
            tie_embeddings=self.tie_embeddings, rope_theta=self.rope_theta,
            mrope=self.mrope)
        if self.moe:
            # the arch's own capacity_factor: drops CAN occur at smoke
            # sizes in training; serving routes dropless
            # (layers.moe_dims_dropless)
            kw["moe"] = MoEConfig(num_experts=4, top_k=2, d_ff_expert=32,
                                  num_shared_experts=self.moe.num_shared_experts
                                  and 1,
                                  capacity_factor=self.moe.capacity_factor,
                                  router_experts=8)
        if self.mla:
            kw["mla"] = MLAConfig(kv_lora_rank=32, qk_nope_head_dim=16,
                                  qk_rope_head_dim=8, v_head_dim=16)
        if self.recurrent:
            kw["recurrent"] = RecurrentConfig(
                lru_width=64, conv_width=4, window=8,
                block_pattern=self.recurrent.block_pattern)
        if self.encoder:
            kw["encoder"] = EncoderConfig(num_layers=2, seq_len=16)
        return ModelConfig(**kw)


# ---------------------------------------------------------------------------
# Input shapes assigned to the LM pool (task spec).
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: Literal["train", "prefill", "decode"]


SHAPES = {
    "train_4k": InputShape("train_4k", 4096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524288, 1, "decode"),
}
