"""Pure-jnp oracles for every Pallas kernel (the allclose references)."""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp


def lora_matmul_ref(x: jax.Array, w0: jax.Array, a: jax.Array, b: jax.Array,
                    scale: float) -> jax.Array:
    """y = x @ W0 + scale · (x @ A) @ B.
    x: (M, K), w0: (K, N), a: (K, R), b: (R, N)."""
    return x @ w0 + scale * ((x @ a) @ b)


def recon_agg_ref(a: jax.Array, b: jax.Array, eta: jax.Array) -> jax.Array:
    """W' = Σ_k η_k · A_k B_k.
    a: (Kc, d_in, r), b: (Kc, r, d_out), eta: (Kc,). Full f32 products,
    as in the kernel: the TPU's default would round the factors to bf16."""
    return jnp.einsum("k,kir,kro->io", eta, a, b,
                      precision=jax.lax.Precision.HIGHEST)


def bgmv_ref(x: jax.Array, a: jax.Array, b: jax.Array, idx: jax.Array
             ) -> jax.Array:
    """y[i] = x[i] @ A[idx[i]] @ B[idx[i]] (multi-LoRA decode gather).
    x: (B, d_in), a: (S, d_in, R), b: (S, R, d_out), idx: (B,) int32."""
    xa = jnp.einsum("bd,bdr->br", x, a[idx])
    return jnp.einsum("br,bro->bo", xa, b[idx])


def paged_attention_ref(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                        page_tables: jax.Array, lengths: jax.Array
                        ) -> jax.Array:
    """Gather-based paged-attention decode oracle (and the off-TPU path).

    q: (B, H, Dh) one decode token per row; k_pool/v_pool:
    (num_pages, page_size, Hkv, Dh); page_tables: (B, P) int32 naming the
    pages that hold row b's positions [j*ps, (j+1)*ps); lengths: (B,)
    valid-token counts. Positions are implicit (slot s of table entry j is
    position j*ps + s) — everything at positions >= lengths[b] is masked.
    Returns (B, H, Dh)."""
    b, h, dh = q.shape
    _, ps, hkv, _ = k_pool.shape
    p = page_tables.shape[1]
    kk = k_pool[page_tables].reshape(b, p * ps, hkv, dh)
    vv = v_pool[page_tables].reshape(b, p * ps, hkv, dh)
    groups = h // hkv
    if groups > 1:
        kk = jnp.broadcast_to(kk[:, :, :, None, :],
                              (b, p * ps, hkv, groups, dh)
                              ).reshape(b, p * ps, h, dh)
        vv = jnp.broadcast_to(vv[:, :, :, None, :],
                              (b, p * ps, hkv, groups, dh)
                              ).reshape(b, p * ps, h, dh)
    scale = 1.0 / math.sqrt(dh)
    logits = jnp.einsum("bhd,bshd->bhs", q.astype(jnp.float32),
                        kk.astype(jnp.float32)) * scale
    valid = jnp.arange(p * ps)[None, :] < lengths[:, None]
    logits = jnp.where(valid[:, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhs,bshd->bhd", probs, vv.astype(jnp.float32))
    # empty rows emit exact zeros (matching the kernel), not the
    # implementation-defined uniform mix of a fully-masked softmax
    out = out * (lengths > 0)[:, None, None]
    return out.astype(q.dtype)


def paged_verify_ref(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                     page_tables: jax.Array, lengths: jax.Array,
                     q_offsets: jax.Array) -> jax.Array:
    """Gather-based multi-query-token paged attention oracle (and the
    off-TPU path of the speculative verify step).

    q: (B, Sq, H, Dh) — Sq speculative tokens per row, token ``i`` of row
    ``b`` at absolute position ``q_offsets[b] + i``; k_pool/v_pool:
    (num_pages, page_size, Hkv, Dh); page_tables: (B, P); lengths: (B,)
    valid-token counts *including* the speculative window. kv positions
    are implicit in the page table; token i attends causally to
    positions <= q_offsets[b] + i (and < lengths[b]). Sq = 1 with
    q_offsets = lengths - 1 is exactly ``paged_attention_ref``.
    Returns (B, Sq, H, Dh); rows with lengths == 0 emit exact zeros."""
    b, sq, h, dh = q.shape
    _, ps, hkv, _ = k_pool.shape
    p = page_tables.shape[1]
    kk = k_pool[page_tables].reshape(b, p * ps, hkv, dh)
    vv = v_pool[page_tables].reshape(b, p * ps, hkv, dh)
    groups = h // hkv
    if groups > 1:
        kk = jnp.broadcast_to(kk[:, :, :, None, :],
                              (b, p * ps, hkv, groups, dh)
                              ).reshape(b, p * ps, h, dh)
        vv = jnp.broadcast_to(vv[:, :, :, None, :],
                              (b, p * ps, hkv, groups, dh)
                              ).reshape(b, p * ps, h, dh)
    scale = 1.0 / math.sqrt(dh)
    logits = jnp.einsum("bqhd,bshd->bqhs", q.astype(jnp.float32),
                        kk.astype(jnp.float32)) * scale
    kv_pos = jnp.arange(p * ps)[None, None, :]                # (1, 1, S)
    qpos = q_offsets[:, None, None] + jnp.arange(sq)[None, :, None]
    valid = (kv_pos < lengths[:, None, None]) & (kv_pos <= qpos)
    logits = jnp.where(valid[:, :, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bqhs,bshd->bqhd", probs, vv.astype(jnp.float32))
    out = out * (lengths > 0)[:, None, None, None]
    return out.astype(q.dtype)


def flash_attention_ref(
    q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool = True,
    window: Optional[int] = None,
) -> jax.Array:
    """Masked softmax attention. q: (Sq, H, D), k/v: (Skv, H, D) —
    single batch element; batch via vmap."""
    sq, h, d = q.shape
    skv = k.shape[0]
    scale = 1.0 / math.sqrt(d)
    logits = jnp.einsum("qhd,khd->hqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    qpos = jnp.arange(sq)[:, None]
    kpos = jnp.arange(skv)[None, :]
    mask = jnp.ones((sq, skv), bool)
    if causal:
        mask &= kpos <= qpos + (skv - sq)   # q may be a suffix of kv
    if window is not None:
        mask &= kpos > qpos + (skv - sq) - window
    logits = jnp.where(mask[None], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, v.astype(jnp.float32)).astype(q.dtype)
