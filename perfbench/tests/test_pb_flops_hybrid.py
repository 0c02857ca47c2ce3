"""The hybrid model's FLOP and byte functions against counts made by
hand."""
import pytest

import flops_hybrid

# d=4, 2 q heads of 2, 1 kv head; Mamba inner 8, state 2, 2 heads of 4,
# conv 2 -> in_proj width 2*8 + 2*2 + 2 = 22; 4 routed experts of width
# 3 (2 held), shared width 5, vocab 10; a Mamba layer then an attention
# layer; LoRA on q and ssm_in
TINY = {"hidden_size": 4, "num_attention_heads": 2, "num_key_value_heads": 1,
        "mamba_expand": 2, "mamba_d_state": 2, "mamba_n_heads": 2,
        "mamba_d_head": 4, "mamba_d_conv": 2, "experts_routed": 4,
        "num_local_experts": 2, "intermediate_size": 3,
        "shared_intermediate_size": 5, "vocab_size": 10,
        "num_hidden_layers": 2, "layer_types": ["mamba", "attention", "x"],
        "lora": {"targets": ["q", "ssm_in"]}}


def test_token_parts_by_hand():
    # router + shared 2*4*4 + 2*3*4*5 = 152 a layer; Mamba in_proj 176,
    # conv 2*2*12 = 48, out_proj 64 -> 288; attention q,k,v 64 + o 32 -> 96
    # LoRA rank 1: ssm_in 2*(4+22) = 52, q 2*(4+4) = 16; SSD 4*2*4*2 = 64
    p = flops_hybrid.token_forward_parts(TINY, 1)
    assert p == {"dense": 688, "ssd": 64, "lora": 68, "first_in": 176}


def test_sequence_by_hand():
    # 3 tokens, 2 predicted, rank 1: per token 2*688 + 3*64 + 3*68 - 176
    # = 1596; attention context (QK, PV) 2*2*2*2 * (1+2+3) = 96, three
    # times with its backward; logits 2*4*10 per predicted token, twice
    assert flops_hybrid.sequence_train_flops(TINY, 3, 2, 1) == \
        3 * 1596 + 3 * 96 + 2 * 160


def test_round_by_hand():
    # two such sequences (clients 0 and 1, rank 1 each) and 5 expert
    # pairs of 2*2*3*4*3 = 144 (forward and input gradient)
    assert flops_hybrid.expert_pair_train_flops(TINY) == 144
    seqs = [(3, 2, 0), (3, 2, 1)]
    assert flops_hybrid.round_flops(TINY, seqs, {0: 1, 1: 1}, 5) == \
        2 * 5396 + 5 * 144


def test_expert_grouped_matmul_cost_by_hand():
    # 5 rows: gate and up 2*5*4*3 each, down 2*5*3*4 -> 360 FLOPs;
    # gate/up read 5x4 rows and 2x4x3 weights and write 5x3: 59 elements,
    # down 5x3 + 2x3x4 + 5x4 = 59; 2 bytes each
    assert flops_hybrid.expert_gmm_cost(TINY, 5) == pytest.approx(
        (360.0, (2 * 59 + 59) * 2.0))
