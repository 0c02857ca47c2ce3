"""The FLOP and byte functions against counts made by hand, and the
peaks table."""
import pytest

import bench
import flops


ENC = {"hidden_size": 4, "intermediate_size": 8, "num_hidden_layers": 2,
       "num_labels": 2, "lora": {"targets": ["q", "v"]}}
DEC = {"hidden_size": 8, "intermediate_size": 16, "num_hidden_layers": 3,
       "vocab_size": 10, "num_attention_heads": 2, "num_key_value_heads": 2,
       "lora": {"targets": ["q", "k", "v", "o"]}}


def test_encoder_train_per_token_by_hand():
    # d=4, ff=8, L=2, seq=3, rank=1, two targets
    # forward per layer: proj 2*4*16=128, mlp 2*2*32=128, attn 4*3*4=48,
    #   lora 2*4*4*1=32 -> 336; two layers 672
    # backward per layer: 128 + 128 + 96 + 64 = 416; two layers 832,
    #   minus layer 0's q/k/v input gradients 3*2*16 = 96 -> 736
    assert flops.encoder_train_flops_per_token(ENC, 3, 1) == 672 + 736


def test_encoder_round_by_hand():
    per = flops.encoder_train_flops_per_token(ENC, 3, 2)
    # 2 clients x 2 steps x 1 row x 3 tokens; head 3*2*4*2 per row
    assert flops.encoder_round_flops(ENC, 2, 2, 1, 3, [1, 3]) == \
        per * 12 + 4 * 48


def test_decoder_token_by_hand():
    # d=8, h=2, dh=4, ff=16, L=3, V=10, ctx=5, rank=2
    # proj 2*8*(8+16) + 2*8*8 = 512; mlp 2*3*8*16 = 768;
    # attn 4*5*8 = 160; lora 4 targets * 4*8*2 = 256 -> 1696 per layer
    assert flops.decode_flops(DEC, 5, 2) == 3 * 1696 + 2 * 8 * 10
    assert flops.decoder_token_flops(DEC, 5, 2, False) == 3 * 1696


def test_prefill_by_hand():
    # prompt 3: attention keys 1+2+3 = 6, each 4*8 FLOPs, over 3 layers
    per = flops.decoder_token_flops(DEC, 0, 1, False)
    assert flops.prefill_flops(DEC, 3, 1) == 3 * per + 3 * 32 * 6 + 160


def test_paged_attn_by_hand():
    f, b = flops.paged_attn_cost(DEC, [3, 5])
    assert f == 4 * 2 * 4 * 8                     # 4 * h * dh * keys
    assert b == 2 * 2 * 4 * 8 * 2 + 2 * 2 * 2 * 4 * 2


def test_flash_attn_by_hand():
    f, b = flops.flash_attn_cost(DEC, 4, 2)       # queries at 4 and 5
    assert f == 4 * 2 * 4 * (5 + 6)
    assert b == 2 * 2 * 4 * 6 * 2 + 2 * 2 * 2 * 4 * 2


def test_roofline_names_its_bound():
    peak = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.roofline_s(200.0, 10.0, peak) == (2.0, "compute")
    assert flops.roofline_s(100.0, 30.0, peak) == (3.0, "memory")


def test_peaks_table_and_unknown_device():
    p = bench.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        bench.peaks("cpu")
