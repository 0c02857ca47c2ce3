"""Multi-query-token paged-attention Pallas kernel — the speculative
verify step.

    o[b, i] = softmax(q[b, i] · K[pages(b)]ᵀ) · V[pages(b)]   i = 0..Sq-1

with a *per-row* causal frontier: q token ``i`` of row ``b`` sits at
absolute position ``q_offsets[b] + i`` and may attend to kv positions
``<= q_offsets[b] + i`` (and ``< lengths[b]``).  This generalizes the
single-token decode kernel (``kernels/paged_attn.py``) to a window of
``Sq`` speculative positions scored in one dispatch: the draft tokens'
K/V are written into the row's pages first, then every draft position is
verified against the target model under exactly the mask plain decode
would have applied one token at a time — which is what makes
draft–verify *lossless* (see ``serve/spec.py``).

q: (B, Sq, Hkv, G, Dh) — Sq speculative tokens per row, query heads
grouped by KV head.  K/V live in the global ``(num_pages(+1),
page_size, Hkv, Dh)`` pool addressed through ``page_tables`` exactly as
in decode; ``q_offsets``/``lengths`` ride in scalar-prefetch SMEM next
to the tables.

TPU mapping: grid (B, Hkv, pages_per_row), page axis innermost and
sequential, carrying online-softmax state for all ``Sq·G`` query rows at
once in VMEM scratch.  The (Sq, G) axes are flattened to one (Sq·G, Dh)
logical q block — the causal row position of flat row ``f`` is
``q_offsets[b] + f // G``.  Pages that lie entirely at-or-past the
row's frontier (``j·page_size >= min(lengths[b], q_offsets[b] + Sq)``)
are skipped with ``pl.when``: the online-softmax state passes through
unchanged, so the skip is output-identical, and short rows in a batch
with one long row no longer pay for the long row's page walk.

Sq = 1 with ``q_offsets = lengths - 1`` reproduces the decode kernel
bit-for-bit (causal ≡ the length mask there); the decode kernel is kept
specialized in ``kernels/paged_attn.py`` for its slimmer scratch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(pt_ref, len_ref, off_ref, q_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref, *, scale: float, page_size: int,
            block_s: int, pages_per_row: int, sq: int, groups: int):
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # A page contributes iff it holds any position below the row's
    # frontier: min(length, offset + Sq) — both bounds live in SMEM, so
    # the whole body (including the MXU work) is skipped for dead pages.
    frontier = jnp.minimum(len_ref[b], off_ref[b] + sq)

    @pl.when(j * page_size < frontier)
    def _attend():
        q = q_ref[0, :, 0].astype(jnp.float32).reshape(sq * groups, -1)
        k = k_ref[0, :, 0, :].astype(jnp.float32)       # (block_s, Dh)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale

        slot = jax.lax.iota(jnp.int32, block_s)
        kv_pos = j * page_size + slot                    # (block_s,)
        qpos = off_ref[b] + jax.lax.iota(jnp.int32, sq * groups) // groups
        valid = (slot[None, :] < page_size) \
            & (kv_pos[None, :] < len_ref[b]) \
            & (kv_pos[None, :] <= qpos[:, None])
        s = jnp.where(valid, s, NEG_INF)

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
        # Rows with nothing to attend to (inactive: length 0) emit exact
        # zeros rather than an implementation-defined uniform mix.
        l = jnp.maximum(l_ref[...], 1e-30)
        out = jnp.where(len_ref[b] > 0, acc_ref[...] / l, 0.0)
        o_ref[0, :, 0] = out.reshape(sq, groups, -1).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("page_size", "scale", "interpret"))
def paged_verify_attention(q, k_pool, v_pool, page_tables, lengths,
                           q_offsets, *, page_size: int,
                           scale: float = None, interpret: bool = False):
    """q: (B, Sq, Hkv, G, Dh), k_pool/v_pool: (NP, block_s, Hkv, Dh),
    page_tables: (B, P) int32, lengths/q_offsets: (B,) int32
    -> (B, Sq, Hkv, G, Dh).

    ``page_size`` is the logical tokens-per-page (block_s may be
    sublane-padded wider); ``scale`` must be supplied when Dh is
    zero-padded.  Hard-asserts lane alignment — call through
    ``ops.paged_verify_attention``, which pads and slices back."""
    bsz, sq, hkv, g, dh = q.shape
    n_pool, block_s, hkv_p, _ = k_pool.shape
    assert hkv_p == hkv and v_pool.shape == k_pool.shape
    pages = page_tables.shape[1]
    assert dh % 128 == 0 and block_s % 8 == 0, (dh, block_s)
    assert 0 < page_size <= block_s
    if scale is None:
        scale = 1.0 / (dh ** 0.5)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(bsz, hkv, pages),
        in_specs=[
            pl.BlockSpec((1, sq, 1, g, dh),
                         lambda i, h, j, pt, ln, off: (i, 0, h, 0, 0)),
            pl.BlockSpec((1, block_s, 1, dh),
                         lambda i, h, j, pt, ln, off: (pt[i, j], 0, h, 0)),
            pl.BlockSpec((1, block_s, 1, dh),
                         lambda i, h, j, pt, ln, off: (pt[i, j], 0, h, 0)),
        ],
        out_specs=pl.BlockSpec((1, sq, 1, g, dh),
                               lambda i, h, j, pt, ln, off: (i, 0, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((sq * g, 1), jnp.float32),
            pltpu.VMEM((sq * g, 1), jnp.float32),
            pltpu.VMEM((sq * g, dh), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, page_size=page_size,
                          block_s=block_s, pages_per_row=pages, sq=sq,
                          groups=g),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bsz, sq, hkv, g, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "parallel", "arbitrary")),
        interpret=interpret,
    )(page_tables.astype(jnp.int32), lengths.astype(jnp.int32),
      q_offsets.astype(jnp.int32), q, k_pool, v_pool)
