"""Batched-gather matrix-vector (BGMV) Pallas kernel for multi-LoRA decode:

    y[i] = x[i] @ A[idx[i]] @ B[idx[i]]        i = 0..B-1

x: (B, d_in), A: (S, d_in, R), B: (S, R, d_out), idx: (B,) int32 — the
serving hot loop where every request in a decode batch carries its own
adapter (S slab slots, heterogeneous ranks zero-padded to R and masked
upstream). This is the S-LoRA/Punica "BGMV" shape specialized to TPU.

TPU mapping: ``idx`` rides in scalar-prefetch memory (SMEM, available
before the body runs) so the BlockSpec index maps steer the DMA engine
directly at A[idx[i]] / B[idx[i]] — the gather costs nothing beyond the
loads the matmul needs anyway, and rows sharing an adapter hit the same
HBM tiles. Grid (B, d_out/bn): one request row per program, the output
dim tiled so a (1, R)·(R, bn) MXU pass closes each tile. x and the
output are viewed as (B, 1, d) with the row dim squeezed from the
blocks: a (1, d_in) block of a (B, d_in) array would break Mosaic's rule
that a block's second-to-last dim be a multiple of 8 or the whole array
dim, while (1, d_in) of (B, 1, d_in) spans it. Per-row VMEM footprint is
(d_in·R + R·bn)·4B — ~1 MB at gemma-2b scale (d=2048, R=128), far under
the ~16 MB budget. All of d_in/d_out/R must be lane-aligned (128);
the ops.py wrapper zero-pads and slices back.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(idx_ref, x_ref, a_ref, b_ref, o_ref):
    del idx_ref  # consumed by the index maps
    xa = jnp.dot(x_ref[...], a_ref[0],
                 preferred_element_type=jnp.float32)          # (1, R)
    o_ref[...] = jnp.dot(xa, b_ref[0].astype(jnp.float32),
                         preferred_element_type=jnp.float32
                         ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def bgmv(x, a, b, idx, *, block_n: int = 256, interpret: bool = False):
    """x: (B, d_in), a: (S, d_in, R), b: (S, R, d_out), idx: (B,) int32
    -> (B, d_out). Hard-asserts lane alignment; call via ops.bgmv."""
    bsz, d_in = x.shape
    s, _, r = a.shape
    d_out = b.shape[-1]
    bn = min(block_n, d_out)
    assert d_in % 128 == 0 and r % 128 == 0 and d_out % bn == 0, \
        (d_in, r, d_out, bn)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bsz, d_out // bn),
        in_specs=[
            pl.BlockSpec((None, 1, d_in),
                         lambda i, j, idx_ref: (i, 0, 0)),               # x
            pl.BlockSpec((1, d_in, r),
                         lambda i, j, idx_ref: (idx_ref[i], 0, 0)),      # A
            pl.BlockSpec((1, r, bn),
                         lambda i, j, idx_ref: (idx_ref[i], 0, j)),      # B
        ],
        out_specs=pl.BlockSpec((None, 1, bn),
                               lambda i, j, idx_ref: (i, 0, j)),
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bsz, 1, d_out), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "parallel")),
        interpret=interpret,
    )(idx.astype(jnp.int32), x[:, None, :], a, b)[:, 0, :]
