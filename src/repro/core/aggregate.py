"""Server-side aggregation strategies — the heart of the paper.

Inputs are *client-stacked* adapters: every leaf has a leading K axis
(clients), possibly followed by stack axes (e.g. layers), then the matrix
axes. Weights ``eta: (K,)`` are the FedAvg coefficients n_k / n.

Three strategies (paper §Methodology):

``aggregate_naive``   Eq. 1 — average A and B *separately*:
                      Ā = Σ η_k A_k,  B̄ = Σ η_k B_k.  With heterogeneous
                      rank masks this is exactly the zero-padding scheme of
                      Cho et al. 2023 (pad to r_max with zeros, average).
                      Biased: (Σ η A)(Σ η B) ≠ Σ η (A B).

``aggregate_hlora``   Eq. 2 + 3 — reconstruct each client's effective
                      update ΔW_k = s_k (A_k·m_k)(B_k·m_k), FedAvg them
                      exactly, re-decompose with SVD and hand each client
                      the optimal (Eckart–Young) rank-r_k truncation.

``aggregate_ensemble``(beyond-paper) — skip the SVD and keep the factored
                      form (Σ r_k columns) when the *server* only needs to
                      evaluate/merge; used by the serving path.

All functions are jit-safe (static shapes via rank masks) and vmap over
any extra stack axes automatically.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import svd as svd_lib
from repro.core.lora import lora_scale, masked_factors

StackedAdapter = Dict[str, jax.Array]  # leaves have leading (K, ...) axes


def _norm_weights(eta: jax.Array) -> jax.Array:
    return eta / jnp.sum(eta)


# ---------------------------------------------------------------------------
# Naive (Eq. 1) — also covers Cho et al. zero-padding via rank masks.
# ---------------------------------------------------------------------------

def aggregate_naive(
    stacked: StackedAdapter, eta: jax.Array, new_masks: Optional[jax.Array] = None
) -> StackedAdapter:
    """Separate averaging of A and B. Returns client-stacked adapters
    (every client gets the same Ā, B̄ masked to its assigned rank)."""
    eta = _norm_weights(eta)
    k = stacked["A"].shape[0]
    ew = eta.reshape((k,) + (1,) * (stacked["A"].ndim - 1))
    # Zero-padding semantics: masked (dead) directions enter the average
    # as zeros — exactly Cho et al.'s padding bias.
    a_m = stacked["A"] * stacked["mask"][..., None, :]
    b_m = stacked["B"] * stacked["mask"][..., :, None]
    a_bar = jnp.sum(ew * a_m, axis=0)
    b_bar = jnp.sum(ew.reshape((k,) + (1,) * (stacked["B"].ndim - 1)) * b_m, axis=0)
    masks = stacked["mask"] if new_masks is None else new_masks
    a_out = jnp.broadcast_to(a_bar[None], stacked["A"].shape)
    b_out = jnp.broadcast_to(b_bar[None], stacked["B"].shape)
    return {"A": a_out, "B": b_out, "mask": masks}


# ---------------------------------------------------------------------------
# HLoRA (Eq. 2 + 3)
# ---------------------------------------------------------------------------

def reconstruct_global_update(
    stacked: StackedAdapter, eta: jax.Array, alpha: float
) -> jax.Array:
    """ΔW' = Σ_k η_k · s_k · (A_k·m_k)(B_k·m_k)   (dense form, Eq. 2)."""
    eta = _norm_weights(eta)
    a, b = masked_factors(stacked)
    scale = lora_scale(stacked, alpha)                   # (K, *stack)
    coef = eta.reshape((-1,) + (1,) * (scale.ndim - 1)) * scale
    return jnp.einsum("k...,k...ir,k...ro->...io", coef, a, b)


def reconstruct_factored(
    stacked: StackedAdapter, eta: jax.Array, alpha: float
) -> Tuple[jax.Array, jax.Array]:
    """ΔW' as (P, Q) with P: (..., d_in, K·r_max), Q: (..., K·r_max, d_out).

    Never materializes the dense (d_in × d_out) update — the coefficient
    η_k·s_k is folded into P. Feeds svd_factored (O(d R²)).
    """
    eta = _norm_weights(eta)
    a, b = masked_factors(stacked)
    scale = lora_scale(stacked, alpha)
    coef = eta.reshape((-1,) + (1,) * (scale.ndim - 1)) * scale
    a = a * coef[..., None, None]
    # (K, *stack, d_in, r) -> (*stack, d_in, K*r)
    k = a.shape[0]
    p = jnp.concatenate([a[i] for i in range(k)], axis=-1)
    q = jnp.concatenate([b[i] for i in range(k)], axis=-2)
    return p, q


def aggregate_hlora(
    stacked: StackedAdapter,
    eta: jax.Array,
    alpha: float,
    new_masks: Optional[jax.Array] = None,
    method: str = "factored",
    split: str = "paper",
    key: Optional[jax.Array] = None,
) -> StackedAdapter:
    """Reconstruct → FedAvg → SVD → per-client rank-r_k redistribution.

    Returns client-stacked adapters such that each client k starts the next
    round from the best rank-r_k approximation of the exact FedAvg update:
        s'_k · (A'_k B'_k) = [ΔW']_{r_k}                       (Eq. 3)
    The client's forward scale s'_k = alpha / r'_k is divided back out of
    the factors so the *effective* update is preserved exactly.
    """
    k = stacked["A"].shape[0]
    r_max = stacked["A"].shape[-1]
    masks = stacked["mask"] if new_masks is None else new_masks

    # Leading stack axes between K and the matrix dims (e.g. layers):
    stack_ndim = stacked["A"].ndim - 3

    if method == "factored":
        p, q = reconstruct_factored(stacked, eta, alpha)
        fn = lambda p_, q_: svd_lib.svd_factored(p_, q_, r_max)
        for _ in range(stack_ndim):
            fn = jax.vmap(fn)
        u, s, vt = fn(p, q)
    elif method in ("exact", "randomized"):
        w = reconstruct_global_update(stacked, eta, alpha)
        if method == "exact":
            fn = lambda w_: svd_lib.svd_exact(w_, r_max)
        else:
            fn = lambda w_: svd_lib.svd_randomized(w_, r_max, key)
        for _ in range(stack_ndim):
            fn = jax.vmap(fn)
        u, s, vt = fn(w)
    else:
        raise ValueError(f"unknown svd method {method!r}")

    a_new, b_new = svd_lib.split_factors(u, s, vt, r_max, split)

    # Per-client: apply the client's mask and undo its forward scale.
    r_eff = jnp.maximum(jnp.sum(masks, axis=-1), 1.0)          # (K, *stack)
    inv_scale = r_eff / alpha
    a_out = a_new[None] * masks[..., None, :]
    b_out = (b_new[None] * masks[..., :, None]) * inv_scale[..., None, None]
    return {"A": a_out, "B": b_out, "mask": masks}


def aggregate_tree(
    adapters: Dict[str, StackedAdapter],
    eta: jax.Array,
    alpha: float,
    strategy: str = "hlora",
    new_masks: Optional[Dict[str, jax.Array]] = None,
    method: str = "factored",
    split: str = "paper",
    key: Optional[jax.Array] = None,
    engine=None,
) -> Dict[str, StackedAdapter]:
    """Apply the chosen aggregation to every LoRA target in the tree.

    Dispatches to the batched :class:`~repro.core.agg_engine.AggregationEngine`
    (one jit-compiled, structure-cached call for the whole tree) — see
    agg_engine.py. ``aggregate_tree_reference`` keeps the per-target loop
    as the equivalence oracle for tests and benchmarks.
    """
    from repro.core import agg_engine
    eng = engine if engine is not None else agg_engine.default_engine()
    out, _spectra = eng(adapters, eta, alpha, strategy=strategy,
                        new_masks=new_masks, method=method, split=split,
                        key=key)
    return out


def aggregate_tree_reference(
    adapters: Dict[str, StackedAdapter],
    eta: jax.Array,
    alpha: float,
    strategy: str = "hlora",
    new_masks: Optional[Dict[str, jax.Array]] = None,
    method: str = "factored",
    split: str = "paper",
    key: Optional[jax.Array] = None,
) -> Dict[str, StackedAdapter]:
    """Seed per-target Python loop — un-batched, un-jitted. Kept as the
    oracle the engine is pinned against (tests/test_engine.py)."""
    out = {}
    for name in sorted(adapters):
        nm = None if new_masks is None else new_masks[name]
        if strategy == "naive":
            out[name] = aggregate_naive(adapters[name], eta, nm)
        elif strategy == "hlora":
            out[name] = aggregate_hlora(
                adapters[name], eta, alpha, nm, method=method, split=split, key=key)
        else:
            raise ValueError(f"unknown strategy {strategy!r}")
    return out


def aggregation_bias(
    stacked: StackedAdapter, eta: jax.Array, alpha: float
) -> jax.Array:
    """‖(Σ η A)(Σ η B) − Σ η (A B)‖_F / ‖Σ η (A B)‖_F  — Eq. 1's bias,
    measured. Zero iff K=1 or all clients happen to agree."""
    exact = reconstruct_global_update(stacked, eta, alpha)
    naive = aggregate_naive(stacked, eta)
    a0 = naive["A"][0] * naive["mask"][0][..., None, :]
    b0 = naive["B"][0] * naive["mask"][0][..., :, None]
    scale = lora_scale({k: v[0] for k, v in naive.items()}, alpha)
    approx = scale[..., None, None] * jnp.einsum("...ir,...ro->...io", a0, b0)
    num = jnp.linalg.norm(exact - approx)
    den = jnp.maximum(jnp.linalg.norm(exact), 1e-30)
    return num / den
