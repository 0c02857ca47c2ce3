"""Fused LoRA matmul Pallas kernel: y = x @ W0 + scale · (x @ A) @ B.

TPU mapping: grid (M/bm, N/bn, K/bk); the K axis is innermost/sequential so
a VMEM f32 scratch accumulates both the base product and the low-rank
bottleneck xA. The LoRA path rides along the W0 tiles — x is read from HBM
once for both products (the fusion the kernel exists for). On the final K
step the (R, bn) B tile closes the low-rank path and the block is written
to HBM exactly once.

Block shapes are the VMEM-footprint knob: (bm·bk + bk·bn)·2B inputs +
(bm·bn + bm·R)·4B scratch must fit ~16 MB VMEM; defaults (256, 256, 512,
R ≤ 128) use ~1.6 MB. MXU alignment: all block dims multiples of 128
(R is zero-padded to 128 lanes by the ops wrapper when smaller).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, w0_ref, a_ref, b_ref, o_ref, acc_ref, xa_ref, *,
            scale: float, k_steps: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        xa_ref[...] = jnp.zeros_like(xa_ref)

    x = x_ref[...]
    acc_ref[...] += jnp.dot(x, w0_ref[...],
                            preferred_element_type=jnp.float32)
    xa_ref[...] += jnp.dot(x, a_ref[...],
                           preferred_element_type=jnp.float32)

    @pl.when(k == k_steps - 1)
    def _finish():
        lo = jnp.dot(xa_ref[...], b_ref[...].astype(jnp.float32),
                     preferred_element_type=jnp.float32)
        o_ref[...] = (acc_ref[...] + scale * lo).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "scale", "block_m", "block_n", "block_k", "interpret"))
def lora_matmul(x, w0, a, b, scale: float = 1.0, *, block_m: int = 256,
                block_n: int = 256, block_k: int = 512,
                interpret: bool = False):
    """x: (M, K), w0: (K, N), a: (K, R), b: (R, N) -> (M, N)."""
    m, k = x.shape
    _, n = w0.shape
    r = a.shape[1]
    bm, bn, bk = min(block_m, m), min(block_n, n), min(block_k, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k, bm, bn, bk)
    k_steps = k // bk
    grid = (m // bm, n // bn, k_steps)
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, k_steps=k_steps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),   # x
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),   # w0
            pl.BlockSpec((bk, r), lambda i, j, kk: (kk, 0)),    # A
            pl.BlockSpec((r, bn), lambda i, j, kk: (0, j)),     # B
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.float32),
            pltpu.VMEM((bm, r), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, w0, a, b)
