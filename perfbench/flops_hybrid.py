"""Operations and bytes the hybrid Mamba-2 / attention / routed-expert
language model (``refs/hybrid_moe_lm.py``) requires to train its LoRA
adapters, from shapes and counts alone.

"Required" as in ``flops.py``: padding positions, logits no loss reads,
recomputation and the rank tail a client does not hold do not count. The
frozen backbone needs activation gradients only (one matmul per forward
matmul), layer 0's input projection needs none (the embedding is frozen),
and each LoRA product needs both factor gradients. The SSD is counted as
its recurrence (a multiply-add to update each state element, another to
read it out), the lower bound of any form of it. Each function is checked
against a hand count in ``tests``.
"""
from __future__ import annotations

from typing import Sequence

ATTN_TARGETS = ("q", "k", "v", "o")


def _g(c: dict) -> dict:
    d = c["hidden_size"]
    h, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    di = c["mamba_expand"] * d
    n, nh = c["mamba_d_state"], c["mamba_n_heads"]
    return {"d": d, "h": h, "hkv": hkv, "dh": d // h, "di": di, "n": n,
            "nh": nh, "p": c["mamba_d_head"], "W": c["mamba_d_conv"],
            "P": 2 * di + 2 * n + nh, "E": c["experts_routed"],
            "ff": c["intermediate_size"],
            "sf": c["shared_intermediate_size"], "V": c["vocab_size"],
            "types": list(c["layer_types"][:c["num_hidden_layers"]]),
            "targets": list(c["lora"]["targets"])}


def _lora_dims(g: dict, t: str) -> int:
    """d_in + d_out of target ``t``."""
    d, dh = g["d"], g["dh"]
    return {"q": d + g["h"] * dh, "k": d + g["hkv"] * dh,
            "v": d + g["hkv"] * dh, "o": g["h"] * dh + d,
            "ssm_in": d + g["P"], "ssm_out": g["di"] + d}[t]


def token_forward_parts(c: dict, rank: float) -> dict:
    """Forward FLOPs of one token through the whole stack, by kind:
    ``dense`` (every frozen matmul but the experts and the logits),
    ``ssd`` (the recurrence), ``lora`` and ``first_in`` (layer 0's input
    projections, whose input gradient is not needed)."""
    g = _g(c)
    d, dh = g["d"], g["dh"]
    moe_dense = 2 * d * g["E"] + 2 * 3 * d * g["sf"]   # router + shared
    mamba = (2 * d * g["P"] + 2 * g["W"] * (g["di"] + 2 * g["n"])
             + 2 * g["di"] * d)
    attn = 2 * d * (g["h"] + 2 * g["hkv"]) * dh + 2 * g["h"] * dh * d
    out = {"dense": 0.0, "ssd": 0.0, "lora": 0.0, "first_in": 0.0}
    for i, kind in enumerate(g["types"]):
        mine = [t for t in g["targets"]
                if (t in ATTN_TARGETS) == (kind == "attention")]
        out["dense"] += moe_dense + (mamba if kind == "mamba" else attn)
        out["lora"] += sum(2 * rank * _lora_dims(g, t) for t in mine)
        if kind == "mamba":
            out["ssd"] += 4 * g["nh"] * g["p"] * g["n"]
        if i == 0:
            out["first_in"] = (2 * d * g["P"] if kind == "mamba"
                               else 2 * d * (g["h"] + 2 * g["hkv"]) * dh)
    return out


def sequence_train_flops(c: dict, length: int, predicted: int,
                         rank: float) -> float:
    """Forward plus backward FLOPs of one sequence of ``length`` real
    tokens, ``predicted`` of which carry a loss (their logits are
    needed), with a rank-``rank`` adapter; routed experts not included
    (:func:`expert_pair_train_flops`). Attention is causal: token i
    attends i + 1 keys (QK and PV, twice that backward); the SSD's and
    the LoRA products' backward is twice their forward."""
    g = _g(c)
    parts = token_forward_parts(c, rank)
    attn_ctx = g["types"].count("attention") * 2 * 2 * g["h"] * g["dh"] \
        * length * (length + 1) / 2
    logits = 2 * g["d"] * g["V"] * predicted
    per_tok = (2 * parts["dense"] + 3 * parts["ssd"] + 3 * parts["lora"]
               - parts["first_in"])
    return float(length * per_tok + 3 * attn_ctx + 2 * logits)


def expert_pair_train_flops(c: dict) -> float:
    """One (token, held expert) pair: the SwiGLU expert forward and its
    input gradient."""
    g = _g(c)
    return float(2 * 2 * 3 * g["d"] * g["ff"])


def round_flops(c: dict, seqs: Sequence[tuple], ranks: Sequence[int],
                pairs: float) -> float:
    """One round's training FLOPs: ``seqs`` (length, predicted, client
    rank index) of every local step of every client, ``ranks`` per
    client, ``pairs`` the (token, held expert) pairs of real tokens.
    Aggregation is negligible beside it and not counted."""
    return float(sum(sequence_train_flops(c, n, m, ranks[k])
                     for n, m, k in seqs)
                 + pairs * expert_pair_train_flops(c))


def expert_gmm_cost(c: dict, pairs: float, itemsize: int = 2) -> tuple:
    """(FLOPs, bytes) of one forward pass of the held experts' three
    grouped matmuls over ``pairs`` sorted rows: gate and up (pairs, d) x
    (held, d, ff), down (pairs, ff) x (held, ff, d); each reads its
    operands and writes its output once."""
    g = _g(c)
    d, ff, held = g["d"], g["ff"], c["num_local_experts"]
    flops = 3 * 2 * pairs * d * ff
    gate_up = pairs * d + held * d * ff + pairs * ff
    down = pairs * ff + held * ff * d + pairs * d
    return float(flops), float((2 * gate_up + down) * itemsize)
