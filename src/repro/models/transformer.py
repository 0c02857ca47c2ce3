"""Decoder-only transformer family (dense / vlm / moe) + RoBERTa-style
encoder classifier. One scanned layer body regardless of depth.

Param tree:
  {"embed": (V,d), "layers": {...stacked (L,...)...}, "final_norm": {...},
   ["lm_head"]: (d,V), ["cls_head"]: (d,C),
   "lora": {target: {"A": (L,d_in,r), "B": (L,r,d_out), "mask": (L,r)}}}
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.core import lora as lora_lib
from repro.models import moe as moe_lib
from repro.models.common import (attention, cache_insert, dense_init,
                                 init_kv_cache, layer_norm, mlp, out_proj,
                                 qkv_proj, rms_norm, rope,
                                 sinusoidal_positions, stacked_dense_init)


def norm(x, p):
    if "b" in p:
        return layer_norm(x, p["w"], p["b"])
    return rms_norm(x, p["w"])


def _norm_init(num_layers, d, use_bias, dtype):
    p = {"w": jnp.zeros((num_layers, d), dtype) if num_layers
         else jnp.zeros((d,), dtype)}
    if use_bias:
        p["w"] = p["w"] + 1.0  # layer_norm multiplies by w directly
        p["b"] = jnp.zeros_like(p["w"])
    return p


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def lora_specs(cfg: ModelConfig) -> Dict[str, Tuple[int, int]]:
    """{target: (d_in, d_out)} for every configured LoRA target."""
    d = cfg.d_model
    hd = cfg.resolved_head_dim
    specs = {}
    for t in cfg.lora.targets:
        if t == "q":
            specs[t] = (d, cfg.num_heads * hd)
        elif t in ("k", "v"):
            specs[t] = (d, cfg.num_kv_heads * hd)
        elif t == "o":
            specs[t] = (cfg.num_heads * hd, d)
        elif t == "w1" or t == "w3":
            specs[t] = (d, cfg.d_ff)
        elif t == "w2":
            specs[t] = (cfg.d_ff, d)
        elif t == "ssm_in":
            di, n = cfg.d_inner, cfg.ssm_state
            specs[t] = (d, 2 * di + 2 * n + cfg.ssm_heads)
        elif t == "ssm_out":
            specs[t] = (cfg.d_inner, d)
        else:
            raise ValueError(f"unknown LoRA target {t!r}")
    return specs


ATTN_TARGETS = ("q", "k", "v", "o")
SSM_TARGETS = ("ssm_in", "ssm_out")


def lora_depths(cfg: ModelConfig) -> Dict[str, int]:
    """{target: how many layers carry it}. Every layer carries every
    target, except in a hybrid_moe pattern, where the attention targets
    live on its attention layers and the SSM ones on its Mamba layers."""
    out = {}
    for t in cfg.lora.targets:
        if cfg.arch_type == "hybrid_moe" and t in ATTN_TARGETS:
            out[t] = cfg.layers_of("attention")
        elif cfg.arch_type == "hybrid_moe" and t in SSM_TARGETS:
            out[t] = cfg.layers_of("mamba")
        else:
            out[t] = cfg.num_layers
    return out


def init_lora(key, cfg: ModelConfig, rank: Optional[int] = None,
              dtype=jnp.float32) -> Dict[str, lora_lib.Adapter]:
    specs = lora_specs(cfg)
    stack = {t: (n,) for t, n in lora_depths(cfg).items()}
    return lora_lib.tree_init(key, specs, cfg.lora.r_max, rank, stack, dtype)


def _init_attn(key, cfg: ModelConfig, L: int, dtype):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": stacked_dense_init(ks[0], L, d, cfg.num_heads * hd, dtype),
        "wk": stacked_dense_init(ks[1], L, d, cfg.num_kv_heads * hd, dtype),
        "wv": stacked_dense_init(ks[2], L, d, cfg.num_kv_heads * hd, dtype),
        "wo": stacked_dense_init(ks[3], L, cfg.num_heads * hd, d, dtype),
    }
    if cfg.use_bias:
        p["bq"] = jnp.zeros((L, cfg.num_heads * hd), dtype)
        p["bk"] = jnp.zeros((L, cfg.num_kv_heads * hd), dtype)
        p["bv"] = jnp.zeros((L, cfg.num_kv_heads * hd), dtype)
        p["bo"] = jnp.zeros((L, d), dtype)
    return p


def _init_mlp(key, cfg: ModelConfig, L: int, dtype):
    d, ff = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    p = {"w1": stacked_dense_init(ks[0], L, d, ff, dtype),
         "w2": stacked_dense_init(ks[1], L, ff, d, dtype)}
    if cfg.activation in ("silu", "geglu"):
        p["w3"] = stacked_dense_init(ks[2], L, d, ff, dtype)
    if cfg.use_bias:
        p["b1"] = jnp.zeros((L, ff), dtype)
        p["b2"] = jnp.zeros((L, d), dtype)
    return p


def init_params(key, cfg: ModelConfig, dtype=jnp.float32):
    L, d = cfg.num_layers, cfg.d_model
    ks = jax.random.split(key, 6)
    layers = {
        "ln1": _norm_init(L, d, cfg.use_bias, dtype),
        "attn": _init_attn(ks[0], cfg, L, dtype),
        "ln2": _norm_init(L, d, cfg.use_bias, dtype),
    }
    if cfg.num_experts:
        layers["mlp"] = moe_lib.init_moe_params(ks[1], cfg, L, dtype)
    else:
        layers["mlp"] = _init_mlp(ks[1], cfg, L, dtype)
    params = {
        "embed": (jax.random.normal(ks[2], (cfg.vocab_size, d)) * 0.02).astype(dtype),
        "layers": layers,
        "final_norm": _norm_init(0, d, cfg.use_bias, dtype),
        "lora": init_lora(ks[3], cfg),
    }
    if cfg.num_classes:
        params["cls_head"] = dense_init(ks[4], d, cfg.num_classes, dtype)
        params["cls_bias"] = jnp.zeros((cfg.num_classes,), dtype)
    elif not cfg.tie_embeddings:
        params["lm_head"] = dense_init(ks[5], d, cfg.vocab_size, dtype)
    return params


# ---------------------------------------------------------------------------
# Layer bodies
# ---------------------------------------------------------------------------

def _layer_adapters(params) -> Dict[str, lora_lib.Adapter]:
    return params["lora"]


def attn_sublayer(x, p, ad, cfg: ModelConfig, *, causal, positions, q_chunk):
    q, k, v = qkv_proj(x, p, cfg, ad)
    if cfg.rope_theta > 0:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    o = attention(q, k, v, causal=causal, window=cfg.sliding_window,
                  q_chunk=q_chunk)
    return out_proj(o, p, cfg, ad)


def decoder_layer(x, lp, ad, cfg: ModelConfig, *, causal=True,
                  positions=None, q_chunk=1024):
    """Pre-norm transformer block. Returns (x, aux)."""
    from repro.models import shard_hints
    x = shard_hints.constrain_tokens(x, x.shape[0])  # anchor batch sharding
    h = attn_sublayer(norm(x, lp["ln1"]), lp["attn"], ad, cfg,
                      causal=causal, positions=positions, q_chunk=q_chunk)
    x = x + h
    aux = jnp.zeros((), jnp.float32)
    if cfg.num_experts:
        y, aux = moe_lib.moe_ffn(norm(x, lp["ln2"]), lp["mlp"], cfg, ad)
    else:
        y = mlp(norm(x, lp["ln2"]), lp["mlp"], cfg, ad)
    return x + y, aux


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------

def forward(params, tokens, cfg: ModelConfig, *, remat=True, q_chunk=1024,
            causal=True):
    """tokens: (B, S) int32 -> (logits (B, S, V) | cls (B, C), aux)."""
    b, s = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0)
    positions = jnp.arange(s)[None, :]
    if cfg.rope_theta == 0:
        # scale content up so absolute positions don't swamp it (as in the
        # original transformer's sqrt(d) embedding scale)
        x = x * math.sqrt(cfg.d_model) + sinusoidal_positions(
            positions, cfg.d_model).astype(x.dtype)

    def layer_fn(x, lp, ad):
        return decoder_layer(x, lp, ad, cfg, causal=causal,
                             positions=positions, q_chunk=q_chunk)

    body = jax.checkpoint(layer_fn) if remat else layer_fn

    def scan_body(carry, xs):
        lp, ad = xs
        x, aux = body(carry, lp, ad)
        return x, aux

    x, auxs = lax.scan(scan_body, x, (params["layers"], _layer_adapters(params)))
    x = norm(x, params["final_norm"])
    if cfg.num_classes:
        pooled = x[:, 0, :]                      # CLS pooling
        logits = pooled @ params["cls_head"] + params["cls_bias"]
        return logits, jnp.sum(auxs)
    head = params.get("lm_head")
    logits = x @ (head if head is not None else params["embed"].T)
    return logits, jnp.sum(auxs)


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_seq: int, dtype=jnp.bfloat16):
    return init_kv_cache(cfg.num_layers, batch, max_seq, cfg.num_kv_heads,
                         cfg.resolved_head_dim, window=cfg.sliding_window,
                         dtype=dtype)


def layer_decode(x, lp, ad, lc, pos, cfg: ModelConfig):
    """One token through one layer with cache. x: (B,1,d)."""
    h = norm(x, lp["ln1"])
    q, k, v = qkv_proj(h, lp["attn"], cfg, ad)
    if cfg.rope_theta > 0:
        pvec = jnp.full((1, 1), pos, jnp.int32)
        q = rope(q, pvec, cfg.rope_theta)
        k = rope(k, pvec, cfg.rope_theta)
    lc = cache_insert(lc, k, v, pos)
    o = attention(
        q, lc["k"], lc["v"], causal=True, window=cfg.sliding_window,
        q_offset=pos, kv_positions=lc["pos"], kv_valid=lc["pos"] >= 0)
    x = x + out_proj(o, lp["attn"], cfg, ad)
    h2 = norm(x, lp["ln2"])
    if cfg.num_experts:
        y, _ = moe_lib.moe_ffn(h2, lp["mlp"], cfg, ad)
    else:
        y = mlp(h2, lp["mlp"], cfg, ad)
    return x + y, lc


def decode_step(params, cache, token, pos, cfg: ModelConfig):
    """token: (B,1) int32, pos: scalar int32 absolute position.
    Returns (logits (B,V), new_cache)."""
    x = jnp.take(params["embed"], token, axis=0)  # (B,1,d)
    if cfg.rope_theta == 0:
        x = x * math.sqrt(cfg.d_model) + sinusoidal_positions(
            jnp.full((1, 1), pos, jnp.int32), cfg.d_model).astype(x.dtype)

    def scan_body(carry, xs):
        lp, ad, lc = xs
        x, new_lc = layer_decode(carry, lp, ad, lc, pos, cfg)
        return x, new_lc

    x, new_cache = lax.scan(
        scan_body, x, (params["layers"], _layer_adapters(params), cache))
    x = norm(x, params["final_norm"])
    head = params.get("lm_head")
    logits = x[:, 0, :] @ (head if head is not None else params["embed"].T)
    return logits, new_cache
