"""Hybrid Mamba-2 / attention decoder with routed experts
(Granite-4.0-H, ``GraniteMoeHybridForCausalLM``), on the training path.

Each layer is a Mamba-2 or a NoPE GQA attention mixer, in the order
``cfg.layer_types`` gives, followed by a routed MoE beside a shared
expert; both branches carry the residual multiplier:

    x = embed[tokens] * embedding_multiplier
    per layer:  x = x + m * Mixer(RMSNorm(x))
                x = x + m * (RoutedHeld(RMSNorm(x)) + Shared(RMSNorm(x)))
    logits = RMSNorm(x) @ embed.T / logits_scaling

Norm gains are ``1 + w`` (``common.rms_norm``): HF's weight is ``1 + w``.
Consecutive layers of one kind run as one ``lax.scan`` over their stack
(one compiled body per kind), each layer rematerialized.

Param tree:
  {"embed": (V, d),
   "mamba":     {"ln1", "ssm": {...}, "ln2", "moe": {...}}  stacked (Lm, ...),
   "attention": {"ln1", "attn": {...}, "ln2", "moe": {...}} stacked (La, ...),
   "final_norm": {"w": (d,)},
   "lora": {q,k,v,o: stacked (La, ...); ssm_in, ssm_out: stacked (Lm, ...)}}
"""
from __future__ import annotations

import itertools
from typing import List, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.models import mamba2 as ssm_lib
from repro.models import moe as moe_lib
from repro.models import transformer as tf_lib
from repro.models.common import attention, out_proj, qkv_proj, rms_norm

KINDS = ("mamba", "attention")


def runs(cfg: ModelConfig) -> List[Tuple[str, int, int]]:
    """Consecutive layers of one kind: (kind, first index in that kind's
    stack, count), in layer order."""
    out, seen = [], {k: 0 for k in KINDS}
    for kind, grp in itertools.groupby(cfg.layer_types):
        n = len(list(grp))
        out.append((kind, seen[kind], n))
        seen[kind] += n
    return out


def init_params(key, cfg: ModelConfig, dtype=jnp.float32):
    d = cfg.d_model
    ks = jax.random.split(key, 6)
    lm, la = cfg.layers_of("mamba"), cfg.layers_of("attention")
    norm0 = tf_lib._norm_init
    return {
        "embed": (jax.random.normal(ks[0], (cfg.vocab_size, d))
                  * 0.02).astype(dtype),
        "mamba": {"ln1": norm0(lm, d, False, dtype),
                  "ssm": ssm_lib.init_ssm_params(ks[1], cfg, lm, dtype),
                  "ln2": norm0(lm, d, False, dtype),
                  "moe": moe_lib.init_routed_params(ks[2], cfg, lm, dtype)},
        "attention": {"ln1": norm0(la, d, False, dtype),
                      "attn": tf_lib._init_attn(ks[3], cfg, la, dtype),
                      "ln2": norm0(la, d, False, dtype),
                      "moe": moe_lib.init_routed_params(ks[4], cfg, la,
                                                        dtype)},
        "final_norm": norm0(0, d, False, dtype),
        "lora": tf_lib.init_lora(ks[5], cfg),
    }


def _moe_branch(x, lp, cfg: ModelConfig):
    y, st = moe_lib.routed_moe(rms_norm(x, lp["ln2"]["w"], cfg.norm_eps),
                               lp["moe"], cfg)
    return x + cfg.residual_multiplier * y, st


def mamba_layer(x, lp, ad, cfg: ModelConfig):
    h = ssm_lib.mamba_mixer(rms_norm(x, lp["ln1"]["w"], cfg.norm_eps),
                            lp["ssm"], cfg, ad)
    return _moe_branch(x + cfg.residual_multiplier * h, lp, cfg)


def attention_layer(x, lp, ad, cfg: ModelConfig, q_chunk: int = 1024):
    h = rms_norm(x, lp["ln1"]["w"], cfg.norm_eps)
    q, k, v = qkv_proj(h, lp["attn"], cfg, ad)
    o = attention(q, k, v, causal=True, q_chunk=q_chunk,
                  scale=cfg.attention_multiplier or None)
    h = out_proj(o, lp["attn"], cfg, ad)
    return _moe_branch(x + cfg.residual_multiplier * h, lp, cfg)


def forward(params, tokens, cfg: ModelConfig, *, remat: bool = True,
            q_chunk: int = 1024):
    """tokens (B, S) -> (logits (B, S, V) f32, stats): ``stats``
    ``moe_load`` (L, held) pairs routed to each held expert and
    ``moe_dropped`` (L,) pairs routed here and not computed, in layer
    order."""
    x = jnp.take(params["embed"], tokens, axis=0) * jnp.asarray(
        cfg.embedding_multiplier, params["embed"].dtype)
    bodies = {"mamba": mamba_layer,
              "attention": lambda x, lp, ad, c: attention_layer(
                  x, lp, ad, c, q_chunk)}
    lora = params["lora"]
    loads, drops = [], []
    for kind, i0, n in runs(cfg):
        stack = params[kind]
        mine = tf_lib.ATTN_TARGETS if kind == "attention" \
            else tf_lib.SSM_TARGETS
        ads = {t: jax.tree.map(lambda a: a[i0:i0 + n], ad)
               for t, ad in lora.items() if t in mine}

        # the layer's weights are indexed inside the rematerialized body,
        # so the backward pass saves the index, not a copy of the weights
        def layer(x, i, ad, stack=stack, body=bodies[kind]):
            return body(x, jax.tree.map(lambda a: a[i], stack), ad, cfg)
        layer = jax.checkpoint(layer) if remat else layer

        def scan_body(x, xs, layer=layer):
            x, st = layer(x, *xs)
            return x, (st["load"], st["dropped"])
        x, (ld, dr) = lax.scan(scan_body, x, (i0 + jnp.arange(n), ads))
        loads.append(ld)
        drops.append(dr)
    x = rms_norm(x, params["final_norm"]["w"], cfg.norm_eps)
    logits = jnp.einsum("bsd,vd->bsv", x, params["embed"],
                        preferred_element_type=jnp.float32)
    return logits / cfg.logits_scaling, {
        "moe_load": jnp.concatenate(loads),
        "moe_dropped": jnp.concatenate(drops)}
