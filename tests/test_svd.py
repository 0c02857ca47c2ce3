"""SVD backends: exact vs factored vs randomized."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import svd


def _low_rank(seed, d_in=40, d_out=32, rank=10):
    key = jax.random.PRNGKey(seed)
    p = jax.random.normal(key, (d_in, rank))
    q = jax.random.normal(jax.random.fold_in(key, 1), (rank, d_out))
    return p, q


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000), r=st.integers(1, 10))
def test_factored_matches_exact(seed, r):
    p, q = _low_rank(seed)
    w = p @ q
    uf, sf, vtf = svd.svd_factored(p, q, r)
    ue, se, vte = svd.svd_exact(w, r)
    np.testing.assert_allclose(sf, se, rtol=1e-4, atol=1e-4)
    # compare reconstructions (U/V sign-ambiguous individually)
    np.testing.assert_allclose((uf * sf) @ vtf, (ue * se) @ vte,
                               rtol=1e-3, atol=1e-3)


def test_randomized_exact_on_low_rank():
    p, q = _low_rank(1, rank=6)
    w = p @ q
    u, s, vt = svd.svd_randomized(w, 6, jax.random.PRNGKey(0), oversample=8)
    ue, se, _ = svd.svd_exact(w, 6)
    np.testing.assert_allclose(s, se, rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose((u * s) @ vt, np.asarray(w), rtol=1e-3,
                               atol=1e-3)


def test_randomized_error_bounded_on_full_rank():
    key = jax.random.PRNGKey(2)
    w = jax.random.normal(key, (64, 48))
    r = 8
    u, s, vt = svd.svd_randomized(w, r, jax.random.PRNGKey(1),
                                  oversample=8, iters=3)
    approx_err = float(jnp.linalg.norm(w - (u * s) @ vt))
    ue, se, vte = svd.svd_exact(w, r)
    best_err = float(jnp.linalg.norm(w - (ue * se) @ vte))
    assert approx_err <= best_err * 1.25  # near-optimal with iterations


@pytest.mark.parametrize("split", ["paper", "sqrt"])
def test_split_factor_products_equal(split):
    p, q = _low_rank(3)
    u, s, vt = svd.svd_factored(p, q, 8)
    a, b = svd.split_factors(u, s, vt, 8, split)
    np.testing.assert_allclose(a @ b, (u[:, :8] * s[:8]) @ vt[:8],
                               rtol=1e-4, atol=1e-4)


def test_truncation_error_decreases_with_rank():
    p, q = _low_rank(4, rank=12)
    w = p @ q
    errs = []
    for r in (2, 4, 8, 12):
        u, s, vt = svd.svd_exact(w, r)
        a, b = svd.split_factors(u, s, vt, r)
        errs.append(float(svd.truncation_error(w, a, b)))
    assert errs == sorted(errs, reverse=True)
    assert errs[-1] < 1e-5  # full rank => exact


@pytest.mark.parametrize("batch", [(5,), (2, 3)], ids=["vmap", "vmap2"])
def test_small_svd_batched_is_per_item(batch):
    """Under vmap the small SVD takes one item at a time, so an item's
    result is the unbatched call's, whatever shares its batch."""
    x = jax.random.normal(jax.random.PRNGKey(3), (*batch, 12, 12))
    fn = svd._svd_small
    for _ in batch:
        fn = jax.vmap(fn)
    got = jax.jit(fn)(x)
    flat = x.reshape(-1, 12, 12)
    for i in range(flat.shape[0]):
        want = jax.jit(svd._svd_small)(flat[i])
        for g, w in zip(got, want):
            np.testing.assert_array_equal(
                np.asarray(g).reshape(-1, *w.shape)[i], np.asarray(w))
