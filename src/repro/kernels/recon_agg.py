"""Reconstruct-aggregate Pallas kernel — the HLoRA server hot-spot (Eq. 2):

    W' = Σ_k η_k · A_k B_k        A: (Kc, d_in, R), B: (Kc, R, d_out)

TPU mapping: grid (d_in/bm, d_out/bn, Kc) with the client axis innermost;
an f32 VMEM scratch accumulates all K clients' rank-R outer products for
one W' tile, and the tile is written to HBM exactly once — versus the
naive formulation's K separate (matmul + add) passes, K HBM read-modify-
writes of the full (d_in × d_out) aggregate. Arithmetic intensity per
tile: 2·bm·bn·R flops over (bm+bn)·R·Kc input bytes. The Kc client
weights sit whole in scalar memory (SMEM) as one (1, Kc) row, each grid
step reading its own: a row whose two dims are the whole array's stays a
legal block when vmap adds a leading batch dim (the engine vmaps the
kernel over every target × layer), where a rank-1 (Kc,) would not.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(eta_ref, a_ref, b_ref, o_ref, acc_ref, *, k_clients: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    eta = eta_ref[0, k]
    acc_ref[...] += eta * jnp.dot(
        a_ref[0], b_ref[0], preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST)

    @pl.when(k == k_clients - 1)
    def _finish():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "block_m", "block_n", "interpret"))
def recon_agg(a, b, eta, *, block_m: int = 256, block_n: int = 256,
              interpret: bool = False):
    """a: (Kc, d_in, R), b: (Kc, R, d_out), eta: (Kc,) -> (d_in, d_out)."""
    kc, d_in, r = a.shape
    d_out = b.shape[-1]
    bm, bn = min(block_m, d_in), min(block_n, d_out)
    assert d_in % bm == 0 and d_out % bn == 0
    grid = (d_in // bm, d_out // bn, kc)
    return pl.pallas_call(
        functools.partial(_kernel, k_clients=kc),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),           # eta (1, Kc)
            pl.BlockSpec((1, bm, r), lambda i, j, k: (k, i, 0)),  # A_k
            pl.BlockSpec((1, r, bn), lambda i, j, k: (k, 0, j)),  # B_k
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((d_in, d_out), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(eta.reshape(1, kc), a, b)
