"""SVD back-ends for HLoRA's server-side re-decomposition (Eq. 3).

Three implementations, trading exactness vs TPU-friendliness:

- ``svd_exact``      — ``jnp.linalg.svd`` on the dense (d_in × d_out) ΔW.
                       The oracle. On TPU this is host-bound / emulated;
                       kept as reference and for tests.
- ``svd_factored``   — **exact** SVD exploiting that the HLoRA aggregate
                       ``ΔW' = Σ_k η_k A_k B_k`` has rank ≤ R = Σ_k r_k ≪ d.
                       QR the stacked tall-skinny factors and SVD only the
                       R×R core: O(d R²) matmul work, MXU-friendly.
                       This is the production server path (beyond-paper).
- ``svd_randomized`` — Halko-style subspace iteration for a dense W when no
                       factored form exists (e.g. aggregating *merged*
                       checkpoints). Approximate, all-matmul.

All return ``(U, s, Vt)`` with shapes (d_in, r), (r,), (r, d_out).
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp


@jax.custom_batching.custom_vmap
def _svd_small(x: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Thin SVD of one small matrix, taken item by item under ``vmap``.

    On the TPU, ``jnp.linalg.svd`` of a batch of small matrices gives an
    item other bits depending on which items share its batch: on a v5e,
    every item of a batch of 48 32x32 matrices differed (by up to 3e-5)
    from the same item in a batch of 12 or of 2, while the Gram products,
    Cholesky QR and eigh around it did not. The sharded aggregation
    engine, whose devices each hold a slice of the batch, then drifts
    from the single-device one. Mapped one item at a time, each result
    depends on its own matrix only."""
    return tuple(jnp.linalg.svd(x, full_matrices=False))


@_svd_small.def_vmap
def _svd_small_vmap(axis_size, in_batched, x):
    del axis_size, in_batched
    return jax.lax.map(_svd_small, x), (True, True, True)


def svd_exact(w: jax.Array, r: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    u, s, vt = jnp.linalg.svd(w, full_matrices=False)
    return u[..., :, :r], s[..., :r], vt[..., :r, :]


def svd_factored(
    p: jax.Array, q: jax.Array, r: int
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Exact top-r SVD of ``p @ q`` without forming it.

    p: (d_in, R), q: (R, d_out) with R ≪ d_in, d_out.
    QR(p) = Qp Rp ; QR(qᵀ) = Qq Rq ; SVD(Rp Rqᵀ) = Û s V̂ᵀ (R×R, cheap);
    U = Qp Û, Vᵀ = (Qq V̂)ᵀ.
    """
    qp, rp = jnp.linalg.qr(p, mode="reduced")          # (d_in,R), (R,R)
    qq, rq = jnp.linalg.qr(q.T, mode="reduced")        # (d_out,R), (R,R)
    core = rp @ rq.T                                    # (R,R)
    uu, s, vvt = _svd_small(core)
    u = qp @ uu
    vt = (qq @ vvt.T).T
    return u[:, :r], s[:r], vt[:r, :]


def _cholqr2(x: jax.Array, shift: float) -> Tuple[jax.Array, jax.Array]:
    """Shifted CholeskyQR2 of a tall-skinny ``x`` (d, R): X = Q R.

    Returns ``(R⁻¹, R)`` rather than ``(Q, R)`` — Q = X R⁻¹ is only ever
    needed applied to r ≪ R columns, so the caller composes the small
    matrices first and pays two thin (d, R)·(R, r) products instead of a
    dense d·R² one.

    Pass 1 factors the shifted Gram G + λI with λ = ``shift``·‖G‖∞ —
    ‖G‖∞ ≥ λmax, so the Cholesky pivots stay ≥ λ even when X is
    numerically rank-deficient (the federated case: every client factor
    is a truncation of the same global adapter, so rank(X) ≈ r ≪ R, and
    a mean-diagonal ridge lands *below* f32 rounding of λmax → NaN).
    Pass 2 re-factors the Gram of Q₁ — computed in data space, where it
    is a sum of squares and therefore PSD to rounding (re-deriving it as
    R₁⁻ᵀ G R₁⁻¹ amplifies G's own f32 negative eigenvalues by 1/λ and
    NaNs) — restoring the orthogonality and σ accuracy the shift gave up
    (Fukaya et al. 2020). Pure BLAS3 + two R×R Choleskys — no Householder
    panel QR.
    """
    rr = x.shape[-1]
    eye = jnp.eye(rr, dtype=x.dtype)

    def _shifted_chol(g, rel):
        lam = rel * jnp.maximum(
            jnp.max(jnp.sum(jnp.abs(g), axis=-1)), 1e-30)  # ‖G‖∞ ≥ λmax
        l = jnp.linalg.cholesky(g + lam * eye)
        return jax.scipy.linalg.solve_triangular(l.T, eye, lower=False), l

    inv1, l1 = _shifted_chol(x.T @ x, shift)              # dR² Gram
    q1 = x @ inv1                                         # ≈ orthonormal
    # Pass-2 shift: G₂ is PSD up to Gram rounding (~R·√d·eps can reach
    # 1e-5 at f32, and DOES go negative when d < R, e.g. wide MLP-down
    # factors), so the guard must sit above that; unlike pass 1 this
    # shift is never corrected, biasing σ by ~shift/2 relative — 3e-5
    # keeps both margins.
    inv2, l2 = _shifted_chol(q1.T @ q1, 3e-5)             # G₂ ≈ I
    rx = l2.T @ l1.T                                      # R = R₂ R₁ (upper)
    return inv1 @ inv2, rx                                # R⁻¹ = R₁⁻¹ R₂⁻¹


def svd_factored_gram(
    p: jax.Array, q: jax.Array, r: int, shift: float = 1e-4
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Top-r SVD of ``p @ q`` via Gram-based QR — the batched engine's
    fast path.

    LAPACK Householder QR of a (d, R) panel is the wall-clock hot spot of
    ``svd_factored`` (measured ~20× the cost of the Gram matmul at server
    scale, and it does not batch). Shifted CholeskyQR2 (see ``_cholqr2``)
    replaces it with pure BLAS3; then as in ``svd_factored``:

        core = Rp Rqᵀ ;  SVD(core) = Û s V̂ᵀ             (R×R, cheap)
        U = Qp Û_r ;  Vᵀ = (Qq V̂_r)ᵀ                    (two thin matmuls)

    Matches the Householder path to ~1e-5 relative Frobenius on the
    rank-r reconstruction at f32, including numerically rank-deficient
    and exactly-masked (zero-column) inputs.
    """
    rinv_p, rp = _cholqr2(p, shift)
    rinv_q, rq = _cholqr2(q.T, shift)
    core = rp @ rq.T                                      # (R, R)
    uu, s, vvt = _svd_small(core)
    u = p @ (rinv_p @ uu[:, :r])                          # Qp Û_r, thin
    vt = (q.T @ (rinv_q @ vvt.T[:, :r])).T                # (Qq V̂_r)ᵀ, thin
    return u, s[:r], vt


@partial(jax.jit, static_argnames=("r", "oversample", "iters"))
def svd_randomized(
    w: jax.Array,
    r: int,
    key: jax.Array,
    oversample: int = 8,
    iters: int = 2,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Randomized range-finder + subspace iteration (Halko et al. 2011).

    Exact (to float precision) when rank(w) ≤ r + oversample, which holds
    for HLoRA aggregates with Σ r_k ≤ r + oversample; otherwise the error
    is bounded by the (r+1)-th singular value. Pure matmul + tall-skinny
    QR — the TPU-native replacement for a LAPACK SVD (DESIGN.md §3).
    """
    d_in, d_out = w.shape
    l = min(r + oversample, min(d_in, d_out))
    omega = jax.random.normal(key, (d_out, l), w.dtype)
    y = w @ omega                                       # (d_in, l)
    # Power/subspace iteration with re-orthonormalization for stability.
    def body(y, _):
        q, _r = jnp.linalg.qr(y, mode="reduced")
        z = w.T @ q                                     # (d_out, l)
        qz, _r2 = jnp.linalg.qr(z, mode="reduced")
        return w @ qz, None
    y, _ = jax.lax.scan(body, y, None, length=iters)
    q, _ = jnp.linalg.qr(y, mode="reduced")             # (d_in, l)
    b = q.T @ w                                         # (l, d_out)
    ub, s, vt = _svd_small(b)
    u = q @ ub
    return u[:, :r], s[:r], vt[:r, :]


def split_factors(
    u: jax.Array, s: jax.Array, vt: jax.Array, r: int, split: str = "paper"
) -> Tuple[jax.Array, jax.Array]:
    """Truncate to rank r and split into (A', B') per Eq. 3.

    'paper':  A' = U_r            B' = Σ_r V_rᵀ   (paper's B'=U, A'=ΣVᵀ,
              transposed into our row-vector convention — see lora.py)
    'sqrt':   A' = U_r √Σ_r       B' = √Σ_r V_rᵀ  (balanced; beyond-paper)
    """
    u_r, s_r, vt_r = u[..., :, :r], s[..., :r], vt[..., :r, :]
    if split == "paper":
        return u_r, s_r[..., :, None] * vt_r
    if split == "sqrt":
        sq = jnp.sqrt(jnp.maximum(s_r, 0.0))
        return u_r * sq[..., None, :], sq[..., :, None] * vt_r
    raise ValueError(f"unknown split {split!r}")


def truncation_error(w: jax.Array, a: jax.Array, b: jax.Array) -> jax.Array:
    """Relative Frobenius error ‖W − AB‖_F / ‖W‖_F."""
    return jnp.linalg.norm(w - a @ b) / jnp.maximum(jnp.linalg.norm(w), 1e-30)
