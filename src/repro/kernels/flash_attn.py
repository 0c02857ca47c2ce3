"""Flash attention Pallas kernel (causal + sliding-window), TPU tiling.

One (head, q-block) program scans KV blocks sequentially (innermost grid
axis), carrying the online-softmax state (running max m, normalizer l,
f32 accumulator) in VMEM scratch. Masks are computed from absolute
positions, so the same kernel serves full-causal and sliding-window
attention (the hymba/long-context path). q may sit at any absolute
offset into the kv sequence: by default q is the suffix
(q_offset = Skv − Sq, the decode contract), but chunked prefill passes
an explicit dynamic offset — it rides in scalar-prefetch SMEM, so every
chunk of a prompt replays one compiled kernel instead of retracing per
offset. KV beyond the chunk's last position (stale pool slots) is
excluded by the same causal mask.

Block shapes: (bq, d) q tile + (bk, d) kv tiles + (bq, bk) logits in VMEM.
Defaults bq = bk = 256 with d ≤ 256 stay well inside 16 MB VMEM.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(qoff_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, causal: bool, window,
            kv_steps: int, block_q: int, block_k: int):
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)              # (bq, d)
    k = k_ref[0].astype(jnp.float32)              # (bk, d)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale

    qpos = (pl.program_id(1) * block_q + jax.lax.iota(jnp.int32, block_q)
            + qoff_ref[0])[:, None]
    kpos = (kb * block_k + jax.lax.iota(jnp.int32, block_k))[None, :]
    mask = jnp.ones(s.shape, jnp.bool_)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v_ref[0].astype(jnp.float32), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(kb == kv_steps - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, ...] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window=None,
                    q_offset=None, block_q: int = 256, block_k: int = 256,
                    interpret: bool = False):
    """q: (Sq, H, D), k/v: (Skv, H, D) -> (Sq, H, D). Batch via vmap.

    ``q_offset``: absolute position of q[0] in the kv sequence. None
    (default) means q is the kv suffix (Skv − Sq). A traced scalar is
    fine — it is delivered via scalar prefetch, not baked into the
    trace, so varying offsets share one compilation."""
    sq, h, d = q.shape
    skv = k.shape[0]
    bq, bk = min(block_q, sq), min(block_k, skv)
    assert sq % bq == 0 and skv % bk == 0
    if q_offset is None:
        q_offset = skv - sq
    qoff = jnp.asarray(q_offset, jnp.int32).reshape(1)
    scale = 1.0 / math.sqrt(d)
    grid = (h, sq // bq, skv // bk)
    qt = jnp.swapaxes(q, 0, 1)   # (H, Sq, D)
    kt = jnp.swapaxes(k, 0, 1)
    vt = jnp.swapaxes(v, 0, 1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda hh, qb, kb, qoff: (hh, qb, 0)),
            pl.BlockSpec((1, bk, d), lambda hh, qb, kb, qoff: (hh, kb, 0)),
            pl.BlockSpec((1, bk, d), lambda hh, qb, kb, qoff: (hh, kb, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d),
                               lambda hh, qb, kb, qoff: (hh, qb, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _kernel, scale=scale, causal=causal, window=window,
            kv_steps=skv // bk, block_q=bq, block_k=bk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((h, sq, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qoff, qt, kt, vt)
    return jnp.swapaxes(out, 0, 1)
