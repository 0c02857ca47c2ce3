"""Granite-4.0-H-Small (32B-A9B) [hybrid_moe] — Mamba-2 + attention +
routed experts [hf:ibm-granite/granite-4.0-h-small].

40L d_model=4096: 36 Mamba-2 mixers (128 heads x 64, d_state 128, one
group, conv 4, chunk 256) and 4 GQA attention mixers (32 q / 8 kv heads x
128, no positional encoding) at layers 5, 15, 25, 35. Every layer ends in
a routed MoE (72 experts, top-10 by logit then a softmax over those 10,
expert width 768) beside an always-on shared expert of width 1536.
muP-style multipliers: embedding x12, attention scores x1/128, residual
branches x0.22, logits /16. Embedding tied to the output; vocab 100352.
"""
from repro.configs.base import LoRAConfig, ModelConfig

_PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4

CONFIG = ModelConfig(
    name="granite-4.0-h-small",
    arch_type="hybrid_moe",
    num_layers=40,
    d_model=4096,
    num_heads=32,
    num_kv_heads=8,
    head_dim=128,
    d_ff=1536,             # shared expert width (shared_intermediate_size)
    vocab_size=100352,
    num_experts=72,
    experts_per_token=10,
    moe_d_ff=768,          # one routed expert's width (intermediate_size)
    moe_shared=True,
    ssm_state=128,
    ssm_expand=2,
    ssm_head_dim=64,
    ssm_conv_width=4,
    ssm_chunk=256,
    layer_types=_PERIOD * 4,
    rope_theta=0.0,
    attention_multiplier=0.0078125,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=16.0,
    norm_eps=1e-5,
    activation="silu",
    tie_embeddings=True,
    lora=LoRAConfig(targets=("q", "k", "v", "o", "ssm_in", "ssm_out"),
                    r_max=16, alpha=32.0),
    source="hf:ibm-granite/granite-4.0-h-small",
)


def reduced() -> ModelConfig:
    return CONFIG.with_(
        name="granite-h-reduced", num_layers=3, d_model=64, num_heads=4,
        num_kv_heads=2, head_dim=16, d_ff=48, vocab_size=128,
        num_experts=8, experts_per_token=3, moe_d_ff=32, ssm_state=16,
        ssm_head_dim=16, ssm_chunk=8,
        layer_types=("mamba", "attention", "mamba"),
        attention_multiplier=1.0 / 16,
        lora=LoRAConfig(targets=("q", "k", "v", "o", "ssm_in", "ssm_out"),
                        r_max=4, alpha=8.0))
