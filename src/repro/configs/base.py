"""Config system: architecture configs, input shapes, registry.

Every assigned architecture gets one file in this package defining
``CONFIG`` (the exact published shape, cited) and ``reduced()`` (a tiny
same-family variant for CPU smoke tests). ``get_config(name)`` /
``list_archs()`` are the public entry points used by --arch flags.
"""
from __future__ import annotations

import dataclasses
import importlib
from dataclasses import dataclass, field
from typing import Optional, Tuple


@dataclass(frozen=True)
class LoRAConfig:
    """HLoRA adapter configuration (the paper's technique)."""
    targets: Tuple[str, ...] = ("q", "k", "v", "o")
    # Static allocation rank: every adapter is allocated at r_max and
    # carries a rank mask (see core/lora.py). Paper: r=8 homogeneous,
    # r_k in [2, 8] heterogeneous.
    r_max: int = 8
    alpha: float = 16.0
    # 'paper'  -> B' = U,    A' = Sigma V^T   (Eq. 3)
    # 'sqrt'   -> B' = U sqrt(Sigma), A' = sqrt(Sigma) V^T (beyond-paper)
    split: str = "paper"


@dataclass(frozen=True)
class ModelConfig:
    name: str
    # dense | moe | ssm | hybrid | hybrid_moe | audio | vlm | encoder
    arch_type: str
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0  # 0 -> d_model // num_heads
    # MoE
    num_experts: int = 0
    experts_per_token: int = 0
    moe_d_ff: int = 0          # per-expert ffn width (defaults to d_ff)
    moe_shared: bool = False   # llama4-style always-on shared expert
    moe_group_size: int = 1024  # tokens per dispatch group (perf knob)
    moe_capacity_factor: float = 1.25   # moe_ffn's; routed_moe is dropless
    # expert parallelism: this device holds experts
    # [moe_expert_offset, moe_expert_offset + moe_experts_held) of the
    # num_experts the router scores (0 held = all of them)
    moe_experts_held: int = 0
    moe_expert_offset: int = 0
    # SSM (mamba2 / hymba)
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    ssm_conv_width: int = 4
    ssm_chunk: int = 128       # SSD chunk length (perf knob, same result)
    # hybrid_moe: the mixer of each layer in order ("mamba" | "attention")
    layer_types: Tuple[str, ...] = ()
    # attention
    sliding_window: Optional[int] = None   # None = full attention
    rope_theta: float = 10000.0
    attention_multiplier: float = 0.0   # score scale; 0 -> 1/sqrt(head_dim)
    # muP-style multipliers (hybrid_moe): embedding output, each residual
    # branch, and the divisor of the logits
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    norm_eps: float = 1e-6
    # ffn
    activation: str = "silu"   # silu | geglu | gelu
    use_bias: bool = False
    # enc-dec (whisper)
    encoder_layers: int = 0
    encoder_seq: int = 1500    # whisper: 30s audio -> 1500 frames
    # encoder-only classification (roberta)
    num_classes: int = 0
    tie_embeddings: bool = False
    lora: LoRAConfig = field(default_factory=LoRAConfig)
    source: str = ""           # citation

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or (self.d_model // max(self.num_heads, 1))

    @property
    def d_inner(self) -> int:  # SSM inner width
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim if self.ssm_state else 0

    @property
    def num_held_experts(self) -> int:
        return self.moe_experts_held or self.num_experts

    def layers_of(self, kind: str) -> int:
        """How many layers of a hybrid_moe pattern have mixer ``kind``."""
        return sum(t == kind for t in self.layer_types)

    @property
    def is_attention_free(self) -> bool:
        return self.arch_type == "ssm"

    @property
    def supports_decode(self) -> bool:
        return self.arch_type not in ("encoder", "hybrid_moe")

    def supports_long_decode(self) -> bool:
        """long_500k eligibility: sub-quadratic decode memory.

        SSM/hybrid natively; dense/moe/vlm only when a sliding window is
        configured (we enable one for the long_500k dry-run variant);
        whisper (a 30 s audio domain) and roberta (no decode step) are
        skipped.
        """
        if self.arch_type in ("ssm", "hybrid"):
            return True
        if self.arch_type in ("audio", "encoder"):
            return False
        return self.sliding_window is not None

    def with_(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    def param_count(self) -> int:
        """Analytic parameter count (base model, excluding LoRA)."""
        d, L = self.d_model, self.num_layers
        hd = self.resolved_head_dim
        emb = self.vocab_size * d
        if self.arch_type == "hybrid_moe":
            attn = d * self.num_heads * hd * 2 + 2 * d * self.num_kv_heads * hd
            di, n = self.d_inner, self.ssm_state
            mamba = d * (2 * di + 2 * n + self.ssm_heads) + di * d
            moe = (d * self.num_experts + self.num_held_experts * 3 * d
                   * self.moe_d_ff + 3 * d * self.d_ff)
            return (emb + self.layers_of("attention") * attn
                    + self.layers_of("mamba") * mamba + L * moe)
        out_head = 0 if self.tie_embeddings else self.vocab_size * d
        if self.num_classes:
            out_head = d * self.num_classes
        per_layer = 0
        if self.arch_type != "ssm":
            q = d * self.num_heads * hd
            kv = 2 * d * self.num_kv_heads * hd
            o = self.num_heads * hd * d
            per_layer += q + kv + o
        if self.arch_type == "ssm" or self.arch_type == "hybrid":
            di = self.d_inner
            # in_proj: x -> [z, x, B, C, dt]
            proj_out = 2 * di + 2 * self.ssm_state + self.ssm_heads
            per_layer += d * proj_out + di * d  # in_proj + out_proj
        if self.num_experts:
            width = self.moe_d_ff or self.d_ff
            per_layer += self.num_experts * 3 * d * width + d * self.num_experts
        elif self.d_ff:
            mult = 3 if self.activation in ("silu", "geglu") else 2
            per_layer += mult * d * self.d_ff
        total = emb + out_head + L * per_layer
        if self.encoder_layers:
            # encoder self-attn + ffn + decoder cross-attn already included
            enc = self.encoder_layers * (4 * d * d + 2 * d * self.d_ff)
            total += enc + L * 4 * d * d  # cross-attention
        return total

    def active_param_count(self) -> int:
        """Active params per token (MoE: top-k experts only)."""
        if not self.num_experts:
            return self.param_count()
        width = self.moe_d_ff or self.d_ff
        inactive = (self.num_experts - self.experts_per_token) * 3 * self.d_model * width
        return self.param_count() - self.num_layers * inactive


@dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode


INPUT_SHAPES = {
    "train_4k": InputShape("train_4k", 4096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524288, 1, "decode"),
}

ARCH_IDS = (
    "hymba_1_5b",
    "mamba2_2_7b",
    "minitron_4b",
    "llama4_maverick_400b_a17b",
    "whisper_small",
    "chameleon_34b",
    "olmoe_1b_7b",
    "granite_34b",
    "gemma_2b",
    "command_r_plus_104b",
    "roberta_large",  # the paper's own model
    "granite_4_0_h_small",
)

_ALIASES = {
    "hymba-1.5b": "hymba_1_5b",
    "mamba2-2.7b": "mamba2_2_7b",
    "minitron-4b": "minitron_4b",
    "llama4-maverick-400b-a17b": "llama4_maverick_400b_a17b",
    "whisper-small": "whisper_small",
    "chameleon-34b": "chameleon_34b",
    "olmoe-1b-7b": "olmoe_1b_7b",
    "granite-34b": "granite_34b",
    "gemma-2b": "gemma_2b",
    "command-r-plus-104b": "command_r_plus_104b",
    "roberta-large": "roberta_large",
}


def canonical(name: str) -> str:
    return _ALIASES.get(name, name.replace("-", "_").replace(".", "_"))


def get_config(name: str) -> ModelConfig:
    mod = importlib.import_module(f"repro.configs.{canonical(name)}")
    return mod.CONFIG


def get_reduced(name: str) -> ModelConfig:
    mod = importlib.import_module(f"repro.configs.{canonical(name)}")
    return mod.reduced()


def list_archs():
    return list(ARCH_IDS)
