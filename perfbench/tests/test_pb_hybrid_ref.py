"""The hybrid language model's plain reference (``refs/hybrid_moe_lm.py``)
against the published implementation (``transformers``'
``GraniteMoeHybridForCausalLM``, its weights copied in) and the program's
model against the reference, on loss and LoRA gradients, at a tiny size
on the CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench
import hybrid_cells
from drivers import fed_lm

REF = bench.load_module(hybrid_cells.fixtures.ROOT
                        + "/perfbench/refs/hybrid_moe_lm.py")


def _setup(**kw):
    c = hybrid_cells.tiny_hybrid(**kw)
    g = REF.dims(c)
    params, lora = REF.make_params(jax.random.PRNGKey(3), c)
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    return c, g, params, lora


def _tokens(c, b=2, s=32):
    return jax.random.randint(jax.random.PRNGKey(5), (b, s), 0,
                              c["vocab_size"])


def _hf_model(c, params):
    torch = pytest.importorskip("torch")
    pytest.importorskip("transformers")
    from transformers.models.granitemoehybrid import (
        GraniteMoeHybridConfig, GraniteMoeHybridForCausalLM)
    g = REF.dims(c)
    hc = GraniteMoeHybridConfig(
        vocab_size=g["V"], hidden_size=g["d"], intermediate_size=g["ff"],
        shared_intermediate_size=g["sf"], num_hidden_layers=g["L"],
        layer_types=list(g["types"]), num_attention_heads=g["h"],
        num_key_value_heads=g["hkv"], attention_multiplier=g["att"],
        embedding_multiplier=g["emb"], residual_multiplier=g["res"],
        logits_scaling=g["logit_div"], rms_norm_eps=g["eps"],
        num_local_experts=g["E"], num_experts_per_tok=g["k"],
        mamba_n_heads=g["nh"], mamba_d_head=g["p"], mamba_d_state=g["n"],
        mamba_d_conv=g["conv"], mamba_expand=c["mamba_expand"],
        mamba_n_groups=1, mamba_chunk_size=c["mamba_chunk_size"],
        mamba_conv_bias=True, mamba_proj_bias=False, attention_bias=False,
        position_embedding_type="nope", tie_word_embeddings=True,
        attn_implementation="eager")
    model = GraniteMoeHybridForCausalLM(hc).eval()
    T = lambda a: torch.tensor(np.asarray(a, np.float32))
    sd = {"model.embed_tokens.weight": T(params["embed"]),
          "model.norm.weight": 1 + T(params["final_norm"]["w"])}
    seen = {"mamba": 0, "attention": 0}
    for li, kind in enumerate(g["types"]):
        i = seen[kind]
        seen[kind] += 1
        lp = jax.tree.map(lambda a: a[i], params[kind])
        pre = f"model.layers.{li}."
        sd[pre + "input_layernorm.weight"] = 1 + T(lp["ln1"]["w"])
        sd[pre + "post_attention_layernorm.weight"] = 1 + T(lp["ln2"]["w"])
        if kind == "mamba":
            s = lp["ssm"]
            sd.update({
                pre + "mamba.in_proj.weight": T(s["in_proj"]).T,
                pre + "mamba.conv1d.weight": T(s["conv_w"]).T[:, None, :],
                pre + "mamba.conv1d.bias": T(s["conv_b"]),
                pre + "mamba.dt_bias": T(s["dt_bias"]),
                pre + "mamba.A_log": T(s["A_log"]),
                pre + "mamba.D": T(s["D"]),
                pre + "mamba.norm.weight": 1 + T(s["ssm_norm"]),
                pre + "mamba.out_proj.weight": T(s["out_proj"]).T})
        else:
            a = lp["attn"]
            for w, name in (("wq", "q"), ("wk", "k"), ("wv", "v"),
                            ("wo", "o")):
                sd[pre + f"self_attn.{name}_proj.weight"] = T(a[w]).T
        m = lp["moe"]
        sd[pre + "block_sparse_moe.router.layer.weight"] = T(m["router"]).T
        sd[pre + "block_sparse_moe.input_linear.weight"] = torch.cat(
            [T(m["we1"]).transpose(1, 2), T(m["we3"]).transpose(1, 2)], 1)
        sd[pre + "block_sparse_moe.output_linear.weight"] = \
            T(m["we2"]).transpose(1, 2)
        sd[pre + "shared_mlp.input_linear.weight"] = torch.cat(
            [T(m["w1"]).T, T(m["w3"]).T], 0)
        sd[pre + "shared_mlp.output_linear.weight"] = T(m["w2"]).T
    missing, unexpected = model.load_state_dict(sd, strict=False)
    assert not unexpected and set(missing) <= {"lm_head.weight"}, missing
    return model, torch


def test_reference_matches_transformers_logits():
    """Every expert held (the published layer): the reference's logits
    equal GraniteMoeHybridForCausalLM's with the same weights."""
    c, g, params, lora = _setup(num_local_experts=8)
    model, torch = _hf_model(c, params)
    toks = _tokens(c)
    with torch.no_grad():
        want = model(torch.tensor(np.asarray(toks))).logits.numpy()
    got = REF.forward_logits(params, lora, toks,
                             gi=tuple(sorted(g.items())))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def _labels(toks):
    s = toks.shape[-1]
    return jnp.where(jnp.arange(s) >= s // 3, jnp.roll(toks, -1, -1), -1)


def test_program_matches_reference_loss_and_lora_grads():
    """The program's hybrid model (held share of 4 of 8 experts, chunked
    SSD, grouped matmul) against the reference (quadratic SSD, dense
    experts) in float32: loss and every LoRA factor's gradient."""
    from repro.models import model as model_lib
    c, g, params, lora = _setup()
    key = jax.random.PRNGKey(11)
    lora = {t: dict(ad, B=0.05 * jax.random.normal(
        jax.random.fold_in(key, i), ad["B"].shape))
        for i, (t, ad) in enumerate(sorted(lora.items()))}
    cfg = fed_lm.model_config(c)
    toks = _tokens(c)
    labels = _labels(toks)
    masks = {t: ad["mask"] for t, ad in lora.items()}
    fac = {t: {"A": ad["A"], "B": ad["B"]} for t, ad in lora.items()}

    def prog_loss(fac):
        full = {t: {**fac[t], "mask": masks[t]} for t in fac}
        return model_lib.loss_fn({**params, "lora": full},
                                 {"tokens": toks, "labels": labels}, cfg,
                                 remat=True)[0]
    lp, gp = jax.value_and_grad(prog_loss)(fac)
    lr, gr = REF.loss_and_grads(params, fac, masks, toks, labels,
                                gi=tuple(sorted(g.items())))
    assert float(lp) == pytest.approx(float(lr), rel=1e-5)
    for t in fac:
        for f in ("A", "B"):
            np.testing.assert_allclose(gp[t][f], gr[t][f], rtol=2e-3,
                                       atol=2e-5 * float(
                                           jnp.abs(gr[t][f]).max()))
