"""Mamba2 (SSD — state-space duality, arXiv:2405.21060) in pure JAX.

Chunked SSD forward: within a chunk the recurrence is materialized as the
semiseparable-matrix form (attention-like, MXU matmuls); across chunks a
``lax.scan`` carries the (H, P, N) state. Chunk length is a perf knob
(memory ∝ chunk², sequential steps ∝ S/chunk).

Decode is the O(1) recurrent step on the carried state; the causal conv
keeps a (width−1)-deep ring buffer in the cache.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.core.lora import Adapter, apply_lora
from repro.models.common import rms_norm

SSD_CHUNK = 128


# ---------------------------------------------------------------------------
# Parameter init (single layer, stacked externally)
# ---------------------------------------------------------------------------

def init_ssm_params(key, cfg: ModelConfig, num_layers: int, dtype):
    d = cfg.d_model
    di = cfg.d_inner
    n = cfg.ssm_state
    h = cfg.ssm_heads
    cw = cfg.ssm_conv_width
    proj_out = 2 * di + 2 * n + h           # [z, x, B, C, dt]
    conv_ch = di + 2 * n                     # conv over x, B, C
    ks = jax.random.split(key, 4)
    std = 1.0 / math.sqrt(d)
    return {
        "in_proj": (jax.random.normal(ks[0], (num_layers, d, proj_out)) * std).astype(dtype),
        "conv_w": (jax.random.normal(ks[1], (num_layers, cw, conv_ch)) * 0.1).astype(dtype),
        "conv_b": jnp.zeros((num_layers, conv_ch), dtype),
        "A_log": jnp.log(jnp.broadcast_to(
            jnp.linspace(1.0, 16.0, h), (num_layers, h)).astype(jnp.float32)),
        "D": jnp.ones((num_layers, h), jnp.float32),
        "dt_bias": jnp.zeros((num_layers, h), jnp.float32),
        "ssm_norm": jnp.zeros((num_layers, di), dtype),
        "out_proj": (jax.random.normal(ks[2], (num_layers, di, d))
                     * (1.0 / math.sqrt(di))).astype(dtype),
    }


# ---------------------------------------------------------------------------
# Causal depthwise conv (training) + ring-buffer step (decode)
# ---------------------------------------------------------------------------

def causal_conv(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """x: (B, S, C), w: (W, C) depthwise. Left-padded causal."""
    width = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    out = sum(xp[:, i:i + x.shape[1], :] * w[i] for i in range(width))
    return jax.nn.silu(out + b)


def conv_step(x_new: jax.Array, buf: jax.Array, w: jax.Array, b: jax.Array):
    """x_new: (B, C) one step; buf: (B, W-1, C) previous inputs."""
    window = jnp.concatenate([buf, x_new[:, None, :]], axis=1)  # (B, W, C)
    out = jnp.einsum("bwc,wc->bc", window, w)
    new_buf = window[:, 1:, :]
    return jax.nn.silu(out + b), new_buf


# ---------------------------------------------------------------------------
# SSD core
# ---------------------------------------------------------------------------

def ssd_chunked(
    x: jax.Array,    # (B, S, H, P)
    dt: jax.Array,   # (B, S, H)   (already softplus'ed)
    a: jax.Array,    # (H,)        (negative)
    bmat: jax.Array, # (B, S, N)
    cmat: jax.Array, # (B, S, N)
    chunk: int = SSD_CHUNK,
    init_state: Optional[jax.Array] = None,  # (B, H, P, N)
) -> Tuple[jax.Array, jax.Array]:
    """Returns (y: (B,S,H,P), final_state: (B,H,P,N)). f32 internals."""
    b, s, h, p = x.shape
    n = bmat.shape[-1]
    chunk = min(chunk, s)
    assert s % chunk == 0, f"seq {s} % chunk {chunk} != 0"
    nc = s // chunk
    f32 = jnp.float32
    xc = x.reshape(b, nc, chunk, h, p).astype(f32)
    dtc = dt.reshape(b, nc, chunk, h).astype(f32)
    bc = bmat.reshape(b, nc, chunk, n).astype(f32)
    cc = cmat.reshape(b, nc, chunk, n).astype(f32)
    a = a.astype(f32)

    state0 = (jnp.zeros((b, h, p, n), f32) if init_state is None
              else init_state.astype(f32))

    idx = jnp.arange(chunk)
    causal = idx[:, None] >= idx[None, :]

    def chunk_body(state, inputs):
        xk, dtk, bk, ck = inputs          # (b,chunk,h,p), (b,chunk,h), (b,chunk,n)×2
        da = dtk * a                       # (b,c,h)
        cum = jnp.cumsum(da, axis=1)       # (b,c,h)
        # intra-chunk: decay L[i,j] = exp(cum_i − cum_j), i ≥ j. The upper
        # triangle has positive exponents -> clamp BEFORE exp so the masked
        # branch can't produce inf (inf·0 = NaN in the backward pass).
        diff = jnp.minimum(cum[:, :, None, :] - cum[:, None, :, :], 0.0)
        ldec = jnp.where(causal[None, :, :, None], jnp.exp(diff), 0.0)
        scores = jnp.einsum("bin,bjn->bij", ck, bk)             # (b,c,c)
        m = scores[..., None] * ldec * dtk[:, None, :, :]       # dt at source j
        y_intra = jnp.einsum("bijh,bjhp->bihp", m, xk)
        # inter-chunk: contribution of the carried state
        decay_in = jnp.exp(cum)                                  # (b,c,h)
        y_inter = jnp.einsum("bin,bhpn->bihp", ck, state) * decay_in[..., None]
        # state update: s' = s·exp(Σda) + Σ_j exp(cum_end − cum_j) dt_j B_j x_j
        chunk_decay = jnp.exp(cum[:, -1, :])                     # (b,h)
        decay_out = jnp.exp(cum[:, -1:, :] - cum) * dtk          # (b,c,h)
        ds = jnp.einsum("bch,bcn,bchp->bhpn", decay_out, bk, xk)
        state_new = state * chunk_decay[:, :, None, None] + ds
        return state_new, y_intra + y_inter

    inputs = (
        jnp.moveaxis(xc, 1, 0), jnp.moveaxis(dtc, 1, 0),
        jnp.moveaxis(bc, 1, 0), jnp.moveaxis(cc, 1, 0))
    # each chunk's (b, c, c, h) decay and score blocks are recomputed in
    # the backward pass rather than kept for every chunk at once
    final_state, ys = lax.scan(jax.checkpoint(chunk_body), state0, inputs)
    y = jnp.moveaxis(ys, 0, 1).reshape(b, s, h, p)
    return y.astype(x.dtype), final_state


def ssd_step(
    state: jax.Array,  # (B, H, P, N)
    x: jax.Array,      # (B, H, P)
    dt: jax.Array,     # (B, H)
    a: jax.Array,      # (H,)
    bvec: jax.Array,   # (B, N)
    cvec: jax.Array,   # (B, N)
) -> Tuple[jax.Array, jax.Array]:
    """One recurrent step. Returns (y: (B,H,P), new_state)."""
    f32 = jnp.float32
    state = state.astype(f32)
    da = jnp.exp(dt.astype(f32) * a.astype(f32))                   # (B,H)
    upd = (dt.astype(f32)[:, :, None, None] * x.astype(f32)[..., None]
           * bvec.astype(f32)[:, None, None, :])
    new_state = state * da[:, :, None, None] + upd
    y = jnp.einsum("bhpn,bn->bhp", new_state, cvec.astype(f32))
    return y.astype(x.dtype), new_state


# ---------------------------------------------------------------------------
# Full mixer block (pre-norm residual handled by the caller)
# ---------------------------------------------------------------------------

def _split_proj(zxbcdt: jax.Array, cfg: ModelConfig):
    di, n, h = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    z = zxbcdt[..., :di]
    xbc = zxbcdt[..., di:di + di + 2 * n]
    dt = zxbcdt[..., di + di + 2 * n:]
    return z, xbc, dt


def gated_norm(y: jax.Array, z: jax.Array, w: jax.Array,
               eps: float) -> jax.Array:
    """RMSNorm of ``y * silu(z)`` over all inner channels, in f32."""
    f32 = jnp.float32
    return rms_norm(y.astype(f32) * jax.nn.silu(z.astype(f32)), w, eps)


def mamba_mixer(
    x: jax.Array, p: Dict[str, jax.Array], cfg: ModelConfig,
    adapters: Optional[Dict[str, Adapter]] = None,
    chunk: Optional[int] = None,
) -> jax.Array:
    """Training/prefill path. x: (B, S, d) -> (B, S, d). The SSD, its
    ``D`` skip and the gated norm run in f32 (the ``ssm.ssd`` scope holds
    the SSD); ``chunk`` defaults to ``cfg.ssm_chunk``."""
    from repro.models import shard_hints
    x = shard_hints.constrain_tokens(x, x.shape[0])
    ad = adapters or {}
    alpha = cfg.lora.alpha
    di, n = cfg.d_inner, cfg.ssm_state
    h, pdim = cfg.ssm_heads, cfg.ssm_head_dim

    zxbcdt = apply_lora(x, p["in_proj"], ad.get("ssm_in"), alpha)
    z, xbc, dt = _split_proj(zxbcdt, cfg)
    xbc = causal_conv(xbc, p["conv_w"], p["conv_b"])
    xs = xbc[..., :di]
    bmat = xbc[..., di:di + n]
    cmat = xbc[..., di + n:]
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    a = -jnp.exp(p["A_log"])
    bsz, s, _ = x.shape
    xh = xs.reshape(bsz, s, h, pdim).astype(jnp.float32)
    with jax.named_scope("ssm.ssd"):
        y, _ = ssd_chunked(xh, dt, a, bmat, cmat,
                           chunk=chunk or cfg.ssm_chunk)
    y = y + p["D"].astype(jnp.float32)[None, None, :, None] * xh
    y = gated_norm(y.reshape(bsz, s, di), z, p["ssm_norm"], cfg.norm_eps)
    return apply_lora(y.astype(x.dtype), p["out_proj"], ad.get("ssm_out"),
                      alpha)


def mamba_mixer_step(
    x: jax.Array,                      # (B, 1, d)
    cache: Dict[str, jax.Array],       # {"conv": (B,W-1,C), "state": (B,H,P,N)}
    p: Dict[str, jax.Array], cfg: ModelConfig,
    adapters: Optional[Dict[str, Adapter]] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    ad = adapters or {}
    alpha = cfg.lora.alpha
    di, n = cfg.d_inner, cfg.ssm_state
    h, pdim = cfg.ssm_heads, cfg.ssm_head_dim

    zxbcdt = apply_lora(x[:, 0, :], p["in_proj"], ad.get("ssm_in"), alpha)
    z = zxbcdt[..., :di]
    xbc = zxbcdt[..., di:di + di + 2 * n]
    dt = zxbcdt[..., di + di + 2 * n:]
    xbc, new_conv = conv_step(xbc, cache["conv"], p["conv_w"], p["conv_b"])
    xs = xbc[..., :di]
    bvec = xbc[..., di:di + n]
    cvec = xbc[..., di + n:]
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    a = -jnp.exp(p["A_log"])
    xh = xs.reshape(-1, h, pdim)
    y, new_state = ssd_step(cache["state"], xh, dt, a, bvec, cvec)
    y = y + p["D"].astype(y.dtype)[None, :, None] * xh
    y = gated_norm(y.reshape(-1, di), z, p["ssm_norm"], cfg.norm_eps)
    out = apply_lora(y.astype(x.dtype), p["out_proj"], ad.get("ssm_out"),
                     alpha)
    return out[:, None, :], {"conv": new_conv, "state": new_state}


def init_ssm_cache(cfg: ModelConfig, num_layers: int, batch: int, dtype):
    di, n = cfg.d_inner, cfg.ssm_state
    return {
        "conv": jnp.zeros((num_layers, batch, cfg.ssm_conv_width - 1, di + 2 * n), dtype),
        "state": jnp.zeros((num_layers, batch, cfg.ssm_heads, cfg.ssm_head_dim, n),
                           jnp.float32),
    }
