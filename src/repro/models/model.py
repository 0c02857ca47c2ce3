"""Unified model API over all architecture families.

    init_params(key, cfg, dtype)            -> params (with params["lora"])
    forward(params, batch, cfg, ...)        -> (logits, aux)
    loss_fn(params, batch, cfg, ...)        -> (loss, metrics)
    init_cache(cfg, batch, max_seq, dtype)  -> cache pytree
    decode_step(params, cache, token, pos, cfg) -> (logits, cache)

``batch``: {"tokens": (B,S) int32, "labels": (B,S)|(B,) int32,
            ["frames"]: (B,S_enc,d) for audio}.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.models import hybrid_moe as hybrid_moe_lib
from repro.models import hymba as hymba_lib
from repro.models import mamba2 as ssm_lib
from repro.models import transformer as tf_lib
from repro.models import whisper as whisper_lib
from repro.models.transformer import norm

MOE_AUX_WEIGHT = 0.01


# ---------------------------------------------------------------------------
# Mamba2 top-level (attention-free stack of mixer blocks)
# ---------------------------------------------------------------------------

def _mamba_init(key, cfg: ModelConfig, dtype):
    ks = jax.random.split(key, 3)
    layers = {
        "ln1": tf_lib._norm_init(cfg.num_layers, cfg.d_model, False, dtype),
        "ssm": ssm_lib.init_ssm_params(ks[0], cfg, cfg.num_layers, dtype),
    }
    return {
        "embed": (jax.random.normal(ks[1], (cfg.vocab_size, cfg.d_model))
                  * 0.02).astype(dtype),
        "layers": layers,
        "final_norm": tf_lib._norm_init(0, cfg.d_model, False, dtype),
        "lora": tf_lib.init_lora(ks[2], cfg),
    }


def _mamba_forward(params, tokens, cfg: ModelConfig, *, remat=True):
    x = jnp.take(params["embed"], tokens, axis=0)

    def layer_fn(x, lp, ad):
        return x + ssm_lib.mamba_mixer(norm(x, lp["ln1"]), lp["ssm"], cfg, ad)

    body = jax.checkpoint(layer_fn) if remat else layer_fn

    def scan_body(carry, xs):
        lp, ad = xs
        return body(carry, lp, ad), None

    x, _ = lax.scan(scan_body, x, (params["layers"], params["lora"]))
    x = norm(x, params["final_norm"])
    return x @ params["embed"].T, jnp.zeros((), jnp.float32)


def _mamba_decode(params, cache, token, pos, cfg: ModelConfig):
    x = jnp.take(params["embed"], token, axis=0)

    def scan_body(carry, xs):
        lp, ad, lc = xs
        h, new_lc = ssm_lib.mamba_mixer_step(
            norm(carry, lp["ln1"]), lc, lp["ssm"], cfg, ad)
        return carry + h, new_lc

    x, new_cache = lax.scan(
        scan_body, x, (params["layers"], params["lora"], cache))
    x = norm(x, params["final_norm"])
    return x[:, 0, :] @ params["embed"].T, new_cache


# ---------------------------------------------------------------------------
# Dispatch
# ---------------------------------------------------------------------------

def init_params(key, cfg: ModelConfig, dtype=jnp.float32):
    if cfg.arch_type == "ssm":
        return _mamba_init(key, cfg, dtype)
    if cfg.arch_type == "hybrid":
        return hymba_lib.init_params(key, cfg, dtype)
    if cfg.arch_type == "hybrid_moe":
        return hybrid_moe_lib.init_params(key, cfg, dtype)
    if cfg.arch_type == "audio":
        return whisper_lib.init_params(key, cfg, dtype)
    return tf_lib.init_params(key, cfg, dtype)  # dense / moe / vlm / encoder


def forward(params, batch: Dict[str, jax.Array], cfg: ModelConfig, *,
            remat: bool = True, q_chunk: int = 1024):
    tokens = batch["tokens"]
    if cfg.arch_type == "ssm":
        return _mamba_forward(params, tokens, cfg, remat=remat)
    if cfg.arch_type == "hybrid":
        return hymba_lib.forward(params, tokens, cfg, remat=remat,
                                 q_chunk=q_chunk)
    if cfg.arch_type == "hybrid_moe":
        return hybrid_moe_lib.forward(params, tokens, cfg, remat=remat,
                                      q_chunk=q_chunk)
    if cfg.arch_type == "audio":
        return whisper_lib.forward(params, tokens, cfg,
                                   frames=batch.get("frames"), remat=remat,
                                   q_chunk=q_chunk)
    causal = cfg.arch_type != "encoder"
    return tf_lib.forward(params, tokens, cfg, remat=remat, q_chunk=q_chunk,
                          causal=causal)


def loss_fn(params, batch, cfg: ModelConfig, *, remat: bool = True,
            q_chunk: int = 1024) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    logits, aux = forward(params, batch, cfg, remat=remat, q_chunk=q_chunk)
    stats = {}
    if isinstance(aux, dict):  # hybrid_moe: routing statistics, no aux loss
        stats, aux = aux, jnp.zeros((), jnp.float32)
    labels = batch["labels"]
    if cfg.num_classes:  # sequence classification (roberta / paper tasks)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1).mean()
        acc = jnp.mean(jnp.argmax(logits, -1) == labels)
        return nll, {"loss": nll, "acc": acc}
    # next-token LM: labels already shifted by the data pipeline.
    # Vocab-parallel CE: logsumexp + iota-pick instead of log_softmax +
    # take_along_axis. The gather form forces GSPMD to all-gather the
    # (B,S,V) logp when vocab is model-sharded (67 GB/device for gemma
    # train_4k); this form reduces over the local vocab shard and
    # all-reduces only (B,S) scalars.
    lg = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lg, axis=-1)                          # (B, S)
    vocab_iota = jnp.arange(lg.shape[-1], dtype=labels.dtype)
    safe = jnp.maximum(labels, 0)
    label_logit = jnp.sum(
        jnp.where(vocab_iota == safe[..., None], lg, 0.0), axis=-1)
    nll = lse - label_logit
    mask = (labels >= 0).astype(jnp.float32)
    nll = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    total = nll + MOE_AUX_WEIGHT * aux
    return total, {"loss": total, "nll": nll, "aux": aux, **stats}


def init_cache(cfg: ModelConfig, batch: int, max_seq: int, dtype=jnp.bfloat16):
    if cfg.arch_type == "ssm":
        return ssm_lib.init_ssm_cache(cfg, cfg.num_layers, batch, dtype)
    if cfg.arch_type == "hybrid":
        return hymba_lib.init_cache(cfg, batch, max_seq, dtype)
    if cfg.arch_type == "audio":
        return whisper_lib.init_cache(cfg, batch, max_seq, dtype)
    if cfg.arch_type == "encoder":
        raise ValueError("encoder-only model has no decode path")
    if cfg.arch_type == "hybrid_moe":
        raise ValueError("hybrid_moe has no decode path (training only)")
    return tf_lib.init_cache(cfg, batch, max_seq, dtype)


def decode_step(params, cache, token, pos, cfg: ModelConfig):
    if cfg.arch_type == "ssm":
        return _mamba_decode(params, cache, token, pos, cfg)
    if cfg.arch_type == "hybrid":
        return hymba_lib.decode_step(params, cache, token, pos, cfg)
    if cfg.arch_type == "audio":
        return whisper_lib.decode_step(params, cache, token, pos, cfg)
    return tf_lib.decode_step(params, cache, token, pos, cfg)
