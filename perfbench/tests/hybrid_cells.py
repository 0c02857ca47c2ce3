"""A tiny cell of the hybrid language-model driver (``fed_lm``) for the
CPU tests: the real driver, reference and limits on a configuration a few
dozen wide, with one Mamba, one attention and one more Mamba layer."""
from __future__ import annotations

import bench
import fixtures

GRANITE = "perfbench/configs/granite-4.0-h-small.json"


def tiny_hybrid(**kw):
    c = fixtures.read(GRANITE)
    c.update(name="hybrid-tiny", num_hidden_layers=3, hidden_size=64,
             num_attention_heads=4, num_key_value_heads=2,
             intermediate_size=32, shared_intermediate_size=48,
             vocab_size=128, mamba_d_head=16, mamba_n_heads=8,
             mamba_d_state=16, mamba_chunk_size=16, experts_routed=8,
             num_local_experts=4, experts_offset=0, num_experts_per_tok=3,
             attention_multiplier=0.0625,
             layer_types=["mamba", "attention", "mamba"])
    c["lora"] = dict(c["lora"], r_max=4, alpha=8)
    c.update(kw)
    return c


def tiny_instruct(**kw):
    t = fixtures.read("perfbench/traffic/instruct.json")
    t.update(clients=12, clients_per_round=3, examples=200, local_steps=2,
             local_batch=1, seq_len=32, prompt_len={"median": 8,
                                                    "sigma": 0.5},
             response_len={"median": 10, "sigma": 0.5}, copy_span=[2, 4],
             rank_range=[2, 4], check_rounds=2, trace_rounds=1)
    t.update(kw)
    return t


def lm_cell(tmp, config=None, traffic=None):
    wl = {"name": "fed_lm_tiny", "config": "hybrid-tiny",
          "traffic": "tiny_instruct", "chips": 1, "why": "test"}
    root = fixtures.make_root(
        tmp, [(wl, config or tiny_hybrid(), traffic or tiny_instruct(),
               fixtures.read("perfbench/limits/fed_instruct_granite.json"))],
        like="fed_instruct_granite")
    return bench.load_cell("fed_lm_tiny", root=root)
