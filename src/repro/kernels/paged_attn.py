"""Paged-attention decode Pallas kernel: one token per row, KV in pages.

    o[b] = softmax(q[b] · K[pages(b)]ᵀ) · V[pages(b)]        b = 0..B-1

q: (B, Hkv, G, Dh) — the decode token's heads grouped by KV head
(G = H / Hkv query heads share each KV head).  K/V live in a global
``(num_pages(+1), page_size, Hkv, Dh)`` pool; row ``b``'s ``j``-th page
id sits in ``page_tables[b, j]`` and holds that row's absolute positions
``[j·page_size, (j+1)·page_size)`` — the fixed-shape page-table contract
from ``serve/pages.py``.  ``lengths[b]`` is the number of valid tokens
(everything at positions >= lengths[b] is unwritten or trash-mapped and
must be masked).

TPU mapping: ``page_tables`` and ``lengths`` ride in scalar-prefetch
memory (SMEM, available before the body runs) so the KV BlockSpec index
maps steer the DMA engine straight at ``pool[page_tables[b, j]]`` — the
page gather costs nothing beyond the loads attention needs anyway (the
same idiom as ``kernels/bgmv.py``'s adapter gather).  Grid
(B, Hkv, pages_per_row): the page axis is innermost and sequential,
carrying online-softmax state (running max m, normalizer l, f32
accumulator) in VMEM scratch exactly like ``kernels/flash_attn.py``.
Padded table entries point at the trash page and are killed by the
length mask, as are the pool's padding slots when the logical
``page_size`` is narrower than the (sublane-aligned) block.

Per-program VMEM: (G, Dh) q + 2·(page_size, Dh) kv + (G, page_size)
logits + scratch — tiny; pages are deliberately small (16–64 tokens).
Pages that sit entirely at-or-past ``lengths[b]`` are fully masked, so
the kernel skips their body with ``pl.when`` on the SMEM-resident
length — output-identical (a skipped page contributes exactly zero to
the online softmax), and ragged batches stop paying the longest row's
page walk on every row.

The multi-query-token generalization (speculative verify: Sq positions
per row with a per-row causal frontier) lives in ``kernels/verify.py``;
this kernel stays specialized to the Sq = 1 decode hot path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(pt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref, *, scale: float, page_size: int,
            block_s: int, pages_per_row: int):
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Pages at-or-past the row's length are fully masked: every slot
    # they hold sits at a position >= lengths[b], so their softmax
    # contribution is exactly zero. Skip the whole body (matmuls
    # included) via the SMEM-resident length — rows much shorter than
    # the longest in the batch stop paying for its page walk.
    @pl.when(j * page_size < len_ref[b])
    def _attend():
        q = q_ref[0, 0].astype(jnp.float32)             # (G, Dh)
        k = k_ref[0, :, 0, :].astype(jnp.float32)       # (block_s, Dh)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale

        slot = jax.lax.iota(jnp.int32, block_s)
        # Logical position of slot s in page j is j*page_size + s; slots
        # past the logical page_size are sublane padding, never valid.
        valid = (slot < page_size) & (j * page_size + slot < len_ref[b])
        s = jnp.where(valid[None, :], s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1,
                                                  keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v_ref[0, :, 0, :].astype(jnp.float32),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == pages_per_row - 1)
    def _finish():
        # Empty rows (length 0) emit exact zeros — fully-masked softmax
        # would otherwise produce an implementation-defined uniform mix.
        l = jnp.maximum(l_ref[...], 1e-30)
        out = jnp.where(len_ref[b] > 0, acc_ref[...] / l, 0.0)
        o_ref[0, 0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("page_size", "scale", "interpret"))
def paged_attention(q, k_pool, v_pool, page_tables, lengths, *,
                    page_size: int, scale: float = None,
                    interpret: bool = False):
    """q: (B, Hkv, G, Dh), k_pool/v_pool: (NP, block_s, Hkv, Dh),
    page_tables: (B, P) int32, lengths: (B,) int32 -> (B, Hkv, G, Dh).

    ``page_size`` is the *logical* tokens-per-page; the pool's slot axis
    (block_s) may be sublane-padded wider.  ``scale`` must be supplied
    when Dh itself is zero-padded (1/sqrt of the *true* head dim).
    Hard-asserts lane alignment — call through ops.paged_attention,
    which pads and slices back."""
    bsz, hkv, g, dh = q.shape
    n_pool, block_s, hkv_p, _ = k_pool.shape
    assert hkv_p == hkv and v_pool.shape == k_pool.shape
    pages = page_tables.shape[1]
    assert dh % 128 == 0 and block_s % 8 == 0, (dh, block_s)
    assert 0 < page_size <= block_s
    if scale is None:
        scale = 1.0 / (dh ** 0.5)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bsz, hkv, pages),
        in_specs=[
            pl.BlockSpec((1, 1, g, dh),
                         lambda i, h, j, pt, ln: (i, h, 0, 0)),        # q
            pl.BlockSpec((1, block_s, 1, dh),
                         lambda i, h, j, pt, ln: (pt[i, j], 0, h, 0)),  # k
            pl.BlockSpec((1, block_s, 1, dh),
                         lambda i, h, j, pt, ln: (pt[i, j], 0, h, 0)),  # v
        ],
        out_specs=pl.BlockSpec((1, 1, g, dh),
                               lambda i, h, j, pt, ln: (i, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, dh), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, page_size=page_size,
                          block_s=block_s, pages_per_row=pages),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bsz, hkv, g, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "parallel", "arbitrary")),
        interpret=interpret,
    )(page_tables.astype(jnp.int32), lengths.astype(jnp.int32),
      q, k_pool, v_pool)
