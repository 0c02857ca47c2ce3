"""Shared model building blocks: norms, RoPE, chunked attention, MLP,
LoRA-wrapped projections, KV caches (full + ring-buffer sliding window).

Everything is a pure function over pytree params — no module framework in
this environment, so params are nested dicts and layers are scanned with
``jax.lax.scan`` over a stacked leading L axis (keeps HLO small: one layer
body regardless of depth — essential for 88-layer granite compiles).
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.core.lora import Adapter, apply_lora

# ---------------------------------------------------------------------------
# Norms & positions
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * (1.0 + w.astype(jnp.float32))).astype(dt)


def layer_norm(x: jax.Array, w: jax.Array, b: jax.Array, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps)
    return (x * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(dt)


def sinusoidal_positions(positions: jax.Array, dim: int) -> jax.Array:
    """(..., ) int positions -> (..., dim) sinusoidal embedding (whisper/
    roberta stand-in for learned positions)."""
    half = dim // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half) / max(half - 1, 1))
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding. x: (B, S, H, Dh), positions: (B, S) or (S,)."""
    dh = x.shape[-1]
    half = dh // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B, S, half)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (chunked over queries — the jnp reference of the Pallas flash
# kernel in repro/kernels/flash_attn.py; memory O(chunk · S_kv))
# ---------------------------------------------------------------------------

def _repeat_kv(k: jax.Array, groups: int) -> jax.Array:
    if groups == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, groups, d)).reshape(
        b, s, h * groups, d)


def attention(
    q: jax.Array,             # (B, Sq, H, Dh)
    k: jax.Array,             # (B, Skv, Hkv, Dh)
    v: jax.Array,             # (B, Skv, Hkv, Dh)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    q_offset: int = 0,        # absolute position of q[0] (prefill continuation)
    kv_positions: Optional[jax.Array] = None,  # (B, Skv) absolute, for caches
    kv_valid: Optional[jax.Array] = None,      # (B, Skv) bool
    q_chunk: int = 1024,
    scale: Optional[float] = None,   # score scale; None -> 1/sqrt(Dh)
) -> jax.Array:
    b, sq, h, dh = q.shape
    hkv = k.shape[2]
    k = _repeat_kv(k, h // hkv)
    v = _repeat_kv(v, h // hkv)
    skv = k.shape[1]
    scale = 1.0 / math.sqrt(dh) if scale is None else scale
    if kv_positions is None:
        kv_pos = jnp.broadcast_to(jnp.arange(skv)[None, :], (b, skv))
    else:
        kv_pos = kv_positions

    def attend_chunk(qc: jax.Array, qpos: jax.Array) -> jax.Array:
        # qc: (B, C, H, Dh); qpos: (C,) absolute positions
        logits = jnp.einsum("bchd,bshd->bhcs", qc.astype(jnp.float32),
                            k.astype(jnp.float32)) * scale
        mask = jnp.ones((b, qc.shape[1], skv), dtype=bool)
        if causal:
            mask &= kv_pos[:, None, :] <= qpos[None, :, None]
        if window is not None:
            mask &= kv_pos[:, None, :] > (qpos[None, :, None] - window)
        if kv_valid is not None:
            mask &= kv_valid[:, None, :]
        logits = jnp.where(mask[:, None, :, :], logits, -1e30)
        p = jax.nn.softmax(logits, axis=-1)
        # probabilities stay f32 against an f32 view of v, as in the
        # flash and paged kernels; only the output takes v's dtype
        out = jnp.einsum("bhcs,bshd->bchd", p, v.astype(jnp.float32))
        return out.astype(v.dtype)

    if sq <= q_chunk:
        return attend_chunk(q, q_offset + jnp.arange(sq))

    if sq % q_chunk:  # largest divisor of sq that fits (static, trace-time)
        q_chunk = max(c for c in range(1, q_chunk + 1) if sq % c == 0)
    n_chunks = sq // q_chunk
    qr = q.reshape(b, n_chunks, q_chunk, h, dh)

    def body(i, _):
        qpos = q_offset + i * q_chunk + jnp.arange(q_chunk)
        return attend_chunk(lax.dynamic_index_in_dim(qr, i, 1, False), qpos)

    out = lax.map(lambda i: body(i, None), jnp.arange(n_chunks))  # (n, B, C, H, Dh)
    return jnp.moveaxis(out, 0, 1).reshape(b, sq, h, dh)


# ---------------------------------------------------------------------------
# KV caches
# ---------------------------------------------------------------------------

def init_kv_cache(
    num_layers: int, batch: int, max_seq: int, kv_heads: int, head_dim: int,
    window: Optional[int] = None, dtype=jnp.bfloat16,
) -> Dict[str, jax.Array]:
    """Full cache (window=None) or ring buffer (window=W: only W slots).
    ``pos`` tracks absolute positions stored in each slot (ring indexing);
    -1 = empty. Stacked over layers for lax.scan."""
    slots = max_seq if window is None else min(window, max_seq)
    return {
        "k": jnp.zeros((num_layers, batch, slots, kv_heads, head_dim), dtype),
        "v": jnp.zeros((num_layers, batch, slots, kv_heads, head_dim), dtype),
        "pos": jnp.full((num_layers, batch, slots), -1, jnp.int32),
    }


def init_paged_kv_pool(
    num_layers: int, num_pages: int, page_size: int, kv_heads: int,
    head_dim: int, dtype=jnp.bfloat16,
) -> Dict[str, jax.Array]:
    """Global paged KV pool shared by every request row (serve/pages.py).

    One extra page beyond ``num_pages`` is the *trash page*: fixed-shape
    jitted steps steer writes for padded/inactive tokens there instead of
    branching, so no live page is ever corrupted. Unlike the dense ring
    cache there is no ``pos`` array — a slot's absolute position is
    implicit in the page table (slot s of a row's j-th page is position
    j * page_size + s), and validity is a per-row length scalar."""
    shape = (num_layers, num_pages + 1, page_size, kv_heads, head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def cache_insert(layer_cache: Dict[str, jax.Array], k_new: jax.Array,
                 v_new: jax.Array, pos: jax.Array) -> Dict[str, jax.Array]:
    """Insert one token (B, 1, Hkv, Dh) at absolute position ``pos`` (scalar).
    Ring buffers wrap at their slot count."""
    slots = layer_cache["k"].shape[1]
    slot = pos % slots
    k = lax.dynamic_update_slice_in_dim(layer_cache["k"], k_new, slot, axis=1)
    v = lax.dynamic_update_slice_in_dim(layer_cache["v"], v_new, slot, axis=1)
    b = k_new.shape[0]
    posu = lax.dynamic_update_slice_in_dim(
        layer_cache["pos"], jnp.full((b, 1), pos, jnp.int32), slot, axis=1)
    return {"k": k, "v": v, "pos": posu}


# ---------------------------------------------------------------------------
# MLP / projections
# ---------------------------------------------------------------------------

def _act(name: str):
    return {"silu": jax.nn.silu, "geglu": jax.nn.gelu, "gelu": jax.nn.gelu}[name]


def mlp(x: jax.Array, p: Dict[str, jax.Array], cfg: ModelConfig,
        adapters: Optional[Dict[str, Adapter]] = None) -> jax.Array:
    """Gated (silu/geglu) or plain (gelu) MLP; optional LoRA on w1/w2/w3."""
    ad = adapters or {}
    alpha = cfg.lora.alpha
    act = _act(cfg.activation)
    h = apply_lora(x, p["w1"], ad.get("w1"), alpha)
    if cfg.use_bias and "b1" in p:
        h = h + p["b1"]
    h = act(h)
    if "w3" in p:  # gated
        g = apply_lora(x, p["w3"], ad.get("w3"), alpha)
        h = h * g
    out = apply_lora(h, p["w2"], ad.get("w2"), alpha)
    if cfg.use_bias and "b2" in p:
        out = out + p["b2"]
    return out


def qkv_proj(
    x: jax.Array, p: Dict[str, jax.Array], cfg: ModelConfig,
    adapters: Optional[Dict[str, Adapter]] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    ad = adapters or {}
    alpha = cfg.lora.alpha
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim
    q = apply_lora(x, p["wq"], ad.get("q"), alpha)
    k = apply_lora(x, p["wk"], ad.get("k"), alpha)
    v = apply_lora(x, p["wv"], ad.get("v"), alpha)
    if cfg.use_bias:
        q = q + p.get("bq", 0.0)
        k = k + p.get("bk", 0.0)
        v = v + p.get("bv", 0.0)
    q = q.reshape(b, s, cfg.num_heads, hd)
    k = k.reshape(b, s, cfg.num_kv_heads, hd)
    v = v.reshape(b, s, cfg.num_kv_heads, hd)
    from repro.models import shard_hints
    if shard_hints.enabled():  # head-aligned resharding (§Perf O2)
        q = shard_hints.constrain_heads(q, b)
        k = shard_hints.constrain_heads(k, b)
        v = shard_hints.constrain_heads(v, b)
    return q, k, v


def out_proj(attn_out: jax.Array, p, cfg: ModelConfig, adapters=None):
    b, s, h, dh = attn_out.shape
    ad = adapters or {}
    y = apply_lora(attn_out.reshape(b, s, h * dh), p["wo"], ad.get("o"),
                   cfg.lora.alpha)
    if cfg.use_bias and "bo" in p:
        y = y + p["bo"]
    return y


# ---------------------------------------------------------------------------
# Init helpers
# ---------------------------------------------------------------------------

def dense_init(key, d_in: int, d_out: int, dtype) -> jax.Array:
    std = 1.0 / math.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * std).astype(dtype)


def stacked_dense_init(key, n: int, d_in: int, d_out: int, dtype) -> jax.Array:
    std = 1.0 / math.sqrt(d_in)
    return (jax.random.normal(key, (n, d_in, d_out), jnp.float32) * std).astype(dtype)
