"""Operations and bytes the algorithms require, from shapes alone.

"Required" means what the mathematics needs, not what the program
happens to compute: padding, a head dimension widened to the lane width,
masked rank columns, logits nobody reads and recomputation do not count.
Each function is checked against a hand count in ``tests``.
"""
from __future__ import annotations

from typing import Sequence


# ---------------------------------------------------------------------------
# Encoder classifier with LoRA, federated training (frozen backbone)
# ---------------------------------------------------------------------------

def encoder_train_flops_per_token(c: dict, seq: int, rank: float) -> float:
    """Forward plus backward FLOPs per trained token.

    The backbone is frozen, so the backward pass needs activation
    gradients only (one matmul per forward matmul), plus both gradients
    of the LoRA factors. Layer 0's input gradient is not needed (the
    embedding is frozen), so its q/k/v projections have no backward."""
    d, ff, L = c["hidden_size"], c["intermediate_size"], \
        c["num_hidden_layers"]
    n_t = len(c["lora"]["targets"])
    proj = 2 * 4 * d * d                  # q, k, v, o
    mlp = 2 * 2 * d * ff
    attn = 2 * 2 * seq * d                # q k^T and p v
    lora = n_t * 2 * 2 * d * rank         # (x A) then (. B)
    fwd = L * (proj + mlp + attn + lora)
    bwd = L * (proj + mlp + 2 * attn + 2 * lora) - 3 * 2 * d * d
    return float(fwd + bwd)


def encoder_round_flops(c: dict, clients: int, steps: int, batch: int,
                        seq: int, ranks: Sequence[int]) -> float:
    """One federated round's training FLOPs (aggregation is negligible
    beside it and not counted)."""
    mean_rank = sum(ranks) / len(ranks)
    d, C = c["hidden_size"], c["num_labels"]
    tokens = clients * steps * batch * seq
    head = clients * steps * batch * 3 * 2 * d * C
    return encoder_train_flops_per_token(c, seq, mean_rank) * tokens + head


# ---------------------------------------------------------------------------
# Decoder language model with LoRA, serving (Phi-3-style MHA block)
# ---------------------------------------------------------------------------

def _dec(c: dict):
    d = c["hidden_size"]
    h = c["num_attention_heads"]
    return (d, c["intermediate_size"], c["num_hidden_layers"],
            c["vocab_size"], h, c["num_key_value_heads"], d // h)


def decoder_token_flops(c: dict, ctx: int, rank: float,
                        logits: bool) -> float:
    """FLOPs of one token that attends ``ctx`` keys (itself included),
    with a rank-``rank`` adapter on every LoRA target, and the vocabulary
    projection where ``logits``."""
    d, ff, L, V, h, hkv, dh = _dec(c)
    proj = 2 * d * (h * dh + 2 * hkv * dh) + 2 * h * dh * d
    mlp = 2 * 3 * d * ff
    attn = 2 * 2 * ctx * h * dh
    lora = len(c["lora"]["targets"]) * 2 * 2 * d * rank
    return float(L * (proj + mlp + attn + lora)
                 + (2 * d * V if logits else 0))


def prefill_flops(c: dict, prompt: int, rank: float) -> float:
    """A whole prompt: every position attends its prefix; only the last
    position's logits are needed."""
    d, ff, L, V, h, hkv, dh = _dec(c)
    per = decoder_token_flops(c, 0, rank, False)
    attn = L * 2 * 2 * h * dh * prompt * (prompt + 1) / 2
    return float(prompt * per + attn + 2 * d * V)


def decode_flops(c: dict, ctx: int, rank: float) -> float:
    return decoder_token_flops(c, ctx, rank, True)


def paged_attn_cost(c: dict, lens: Sequence[int],
                    itemsize: int = 2) -> tuple:
    """(FLOPs, bytes) of one layer's decode attention over rows holding
    ``lens`` valid tokens each: every row reads its own K and V once."""
    d, ff, L, V, h, hkv, dh = _dec(c)
    n = float(sum(lens))
    flops = 2 * 2 * h * dh * n
    kv = 2 * hkv * dh * n * itemsize
    qo = 2 * len(lens) * h * dh * itemsize
    return flops, kv + qo


def flash_attn_cost(c: dict, pos0: int, nvalid: int,
                    itemsize: int = 2) -> tuple:
    """(FLOPs, bytes) of one layer's causal attention for a prefill
    chunk of ``nvalid`` tokens at offset ``pos0``: query i attends keys
    0..pos0+i."""
    d, ff, L, V, h, hkv, dh = _dec(c)
    keys = nvalid * pos0 + nvalid * (nvalid + 1) / 2
    flops = 2 * 2 * h * dh * keys
    kv = 2 * hkv * dh * (pos0 + nvalid) * itemsize
    qo = 2 * nvalid * h * dh * itemsize
    return float(flops), float(kv + qo)


def roofline_s(flops: float, nbytes: float, peak: dict) -> tuple:
    """(least seconds, bound) at the chip's peaks."""
    t_c = flops / peak["bf16_flops"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
