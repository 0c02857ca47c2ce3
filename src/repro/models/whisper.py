"""Whisper-small backbone (arXiv:2212.04356): transformer encoder-decoder.

Per spec the mel-spectrogram + conv frontend is a STUB — the model consumes
precomputed frame embeddings ``frames: (B, S_enc, d_model)`` (what the conv
stack would emit). Everything downstream — bidirectional encoder, causal
decoder with cross-attention, KV caches — is fully implemented.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.models import transformer as tf_lib
from repro.models.common import (attention, cache_insert, init_kv_cache,
                                 mlp, out_proj, qkv_proj,
                                 sinusoidal_positions)
from repro.models.transformer import norm


def init_params(key, cfg: ModelConfig, dtype=jnp.float32):
    d = cfg.d_model
    Le, Ld = cfg.encoder_layers, cfg.num_layers
    ks = jax.random.split(key, 8)
    enc_layers = {
        "ln1": tf_lib._norm_init(Le, d, True, dtype),
        "attn": tf_lib._init_attn(ks[0], cfg, Le, dtype),
        "ln2": tf_lib._norm_init(Le, d, True, dtype),
        "mlp": tf_lib._init_mlp(ks[1], cfg, Le, dtype),
    }
    dec_layers = {
        "ln1": tf_lib._norm_init(Ld, d, True, dtype),
        "attn": tf_lib._init_attn(ks[2], cfg, Ld, dtype),
        "lnx": tf_lib._norm_init(Ld, d, True, dtype),
        "xattn": tf_lib._init_attn(ks[3], cfg, Ld, dtype),
        "ln2": tf_lib._norm_init(Ld, d, True, dtype),
        "mlp": tf_lib._init_mlp(ks[4], cfg, Ld, dtype),
    }
    return {
        "embed": (jax.random.normal(ks[5], (cfg.vocab_size, d)) * 0.02).astype(dtype),
        "encoder": enc_layers,
        "enc_norm": tf_lib._norm_init(0, d, True, dtype),
        "layers": dec_layers,
        "final_norm": tf_lib._norm_init(0, d, True, dtype),
        # whisper ties decoder embedding to the output head
        "lora": tf_lib.init_lora(ks[6], cfg),  # decoder self-attn q/v
    }


def encode(params, frames, cfg: ModelConfig):
    """frames: (B, S_enc, d) stub embeddings -> (B, S_enc, d)."""
    s = frames.shape[1]
    x = frames + sinusoidal_positions(
        jnp.arange(s)[None, :], cfg.d_model).astype(frames.dtype)

    def scan_body(carry, lp):
        h = norm(carry, lp["ln1"])
        q, k, v = qkv_proj(h, lp["attn"], cfg, None)
        att = attention(q, k, v, causal=False)
        x = carry + out_proj(att, lp["attn"], cfg, None)
        y = mlp(norm(x, lp["ln2"]), lp["mlp"], cfg, None)
        return x + y, None

    x, _ = lax.scan(scan_body, x, params["encoder"])
    return norm(x, params["enc_norm"])


def dec_layer(x, lp, ad, enc_kv, cfg: ModelConfig, *, positions, q_chunk):
    """enc_kv: cross K/V computed from enc_out by the caller's closure."""
    from repro.models import shard_hints
    x = shard_hints.constrain_tokens(x, x.shape[0])
    h = norm(x, lp["ln1"])
    q, k, v = qkv_proj(h, lp["attn"], cfg, ad)
    att = attention(q, k, v, causal=True, q_chunk=q_chunk)
    x = x + out_proj(att, lp["attn"], cfg, ad)
    # cross-attention
    hx = norm(x, lp["lnx"])
    qx = apply_q(hx, lp["xattn"], cfg)
    kx, vx = enc_kv
    attx = attention(qx, kx, vx, causal=False, q_chunk=q_chunk)
    x = x + out_proj(attx, lp["xattn"], cfg, None)
    y = mlp(norm(x, lp["ln2"]), lp["mlp"], cfg, ad)
    return x + y


def apply_q(x, p, cfg: ModelConfig):
    b, s, _ = x.shape
    q = x @ p["wq"]
    if cfg.use_bias:
        q = q + p["bq"]
    return q.reshape(b, s, cfg.num_heads, cfg.resolved_head_dim)


def cross_kv(enc_out, p, cfg: ModelConfig):
    b, s, _ = enc_out.shape
    hd = cfg.resolved_head_dim
    k = (enc_out @ p["wk"] + (p["bk"] if cfg.use_bias else 0.0))
    v = (enc_out @ p["wv"] + (p["bv"] if cfg.use_bias else 0.0))
    return (k.reshape(b, s, cfg.num_kv_heads, hd),
            v.reshape(b, s, cfg.num_kv_heads, hd))


def forward(params, tokens, cfg: ModelConfig, *, frames=None, remat=True,
            q_chunk=1024):
    """Teacher-forced training forward. Returns (logits, aux=0)."""
    assert frames is not None, "whisper needs frame embeddings"
    enc_out = encode(params, frames, cfg)
    b, s = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0)
    x = x * (cfg.d_model ** 0.5) + sinusoidal_positions(
        jnp.arange(s)[None, :], cfg.d_model).astype(x.dtype)
    positions = jnp.arange(s)[None, :]

    def layer_fn(x, lp, ad):
        kv = cross_kv(enc_out, lp["xattn"], cfg)
        return dec_layer(x, lp, ad, kv, cfg, positions=positions,
                         q_chunk=q_chunk)

    body = jax.checkpoint(layer_fn) if remat else layer_fn

    def scan_body(carry, xs):
        lp, ad = xs
        return body(carry, lp, ad), None

    x, _ = lax.scan(scan_body, x, (params["layers"], params["lora"]))
    x = norm(x, params["final_norm"])
    return x @ params["embed"].T, jnp.zeros((), jnp.float32)


def init_cache(cfg: ModelConfig, batch: int, max_seq: int, dtype=jnp.bfloat16):
    """Self-attn cache + precomputed cross K/V (filled at prefill from the
    encoder output; zeros here)."""
    hd = cfg.resolved_head_dim
    return {
        "self": init_kv_cache(cfg.num_layers, batch, max_seq,
                              cfg.num_kv_heads, hd, dtype=dtype),
        "cross_k": jnp.zeros((cfg.num_layers, batch, cfg.encoder_seq,
                              cfg.num_kv_heads, hd), dtype),
        "cross_v": jnp.zeros((cfg.num_layers, batch, cfg.encoder_seq,
                              cfg.num_kv_heads, hd), dtype),
    }


def prefill_cache(params, frames, cfg: ModelConfig, batch: int, max_seq: int,
                  dtype=jnp.bfloat16):
    """Run the encoder and fill the cross K/V for serving."""
    enc_out = encode(params, frames, cfg)

    def per_layer(lp):
        return cross_kv(enc_out, lp["xattn"], cfg)

    ks, vs = jax.vmap(per_layer)(params["layers"])
    cache = init_cache(cfg, batch, max_seq, dtype)
    return {**cache, "cross_k": ks.astype(dtype), "cross_v": vs.astype(dtype)}


def decode_step(params, cache, token, pos, cfg: ModelConfig):
    x = jnp.take(params["embed"], token, axis=0)
    x = x * (cfg.d_model ** 0.5) + sinusoidal_positions(
        jnp.full((1, 1), pos, jnp.int32), cfg.d_model).astype(x.dtype)

    def scan_body(carry, xs):
        lp, ad, lc, ck, cv = xs
        h = norm(carry, lp["ln1"])
        q, k, v = qkv_proj(h, lp["attn"], cfg, ad)
        lc = cache_insert(lc, k, v, pos)
        att = attention(q, lc["k"], lc["v"], causal=True, q_offset=pos,
                        kv_positions=lc["pos"], kv_valid=lc["pos"] >= 0)
        x = carry + out_proj(att, lp["attn"], cfg, ad)
        hx = norm(x, lp["lnx"])
        qx = apply_q(hx, lp["xattn"], cfg)
        attx = attention(qx, ck, cv, causal=False)
        x = x + out_proj(attx, lp["xattn"], cfg, None)
        y = mlp(norm(x, lp["ln2"]), lp["mlp"], cfg, ad)
        return x + y, lc

    x, new_self = lax.scan(
        scan_body, x,
        (params["layers"], params["lora"], cache["self"],
         cache["cross_k"], cache["cross_v"]))
    x = norm(x, params["final_norm"])
    logits = x[:, 0, :] @ params["embed"].T
    return logits, {**cache, "self": new_self}
