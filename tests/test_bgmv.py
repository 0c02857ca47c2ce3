"""BGMV kernel property tests against the jnp oracle (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(0)


def _inputs(bsz, d_in, d_out, s, r, seed):
    ks = jax.random.split(jax.random.fold_in(KEY, seed), 4)
    x = jax.random.normal(ks[0], (bsz, d_in))
    a = jax.random.normal(ks[1], (s, d_in, r)) * 0.1
    b = jax.random.normal(ks[2], (s, r, d_out)) * 0.1
    idx = jax.random.randint(ks[3], (bsz,), 0, s)
    return x, a, b, idx


@settings(max_examples=6, deadline=None)
@given(d_in=st.sampled_from([64, 96, 128, 200]),
       d_out=st.sampled_from([64, 160, 256]),
       bsz=st.integers(min_value=1, max_value=9),
       s=st.integers(min_value=1, max_value=5),
       seed=st.integers(min_value=0, max_value=10_000))
def test_bgmv_nonaligned_dims(d_in, d_out, bsz, s, seed):
    """Feature dims off the 128 lane grid: wrapper pads and slices back."""
    x, a, b, idx = _inputs(bsz, d_in, d_out, s, 8, seed)
    y = ops.bgmv(x, a, b, idx)
    assert y.shape == (bsz, d_out)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(ref.bgmv_ref(x, a, b, idx)),
                               rtol=2e-4, atol=2e-4)


@settings(max_examples=6, deadline=None)
@given(r_slab=st.sampled_from([4, 8, 16]),
       s=st.integers(min_value=2, max_value=6),
       seed=st.integers(min_value=0, max_value=10_000))
def test_bgmv_ragged_ranks(r_slab, s, seed):
    """Heterogeneous true ranks inside one slab: masking A's dead columns
    makes the padded result exactly the rank-r_k truncated product."""
    bsz = 8
    x, a, b, idx = _inputs(bsz, 128, 128, s, r_slab, seed)
    ranks = np.asarray(jax.random.randint(
        jax.random.fold_in(KEY, seed + 1), (s,), 1, r_slab + 1))
    mask = (np.arange(r_slab)[None, :] < ranks[:, None]).astype(np.float32)
    am = a * jnp.asarray(mask)[:, None, :]
    y = np.asarray(ops.bgmv(x, am, b, idx))
    for i in range(bsz):
        k = int(idx[i])
        r_k = int(ranks[k])
        want = np.asarray(x[i]) @ np.asarray(a[k][:, :r_k]) \
            @ np.asarray(b[k][:r_k, :])
        np.testing.assert_allclose(y[i], want, rtol=2e-4, atol=2e-4)


@settings(max_examples=6, deadline=None)
@given(bsz=st.integers(min_value=2, max_value=12),
       slot=st.integers(min_value=0, max_value=2),
       seed=st.integers(min_value=0, max_value=10_000))
def test_bgmv_repeated_indices(bsz, slot, seed):
    """Many rows sharing one adapter (the common traffic shape): rows with
    equal idx and equal inputs produce identical outputs, and everything
    matches the oracle."""
    x, a, b, _ = _inputs(bsz, 128, 128, 3, 8, seed)
    x = x.at[1].set(x[0])                      # duplicate row 0's input
    idx = jnp.full((bsz,), slot, jnp.int32).at[2:].set(
        jax.random.randint(jax.random.fold_in(KEY, seed + 2),
                           (max(bsz - 2, 0),), 0, 3))
    y = np.asarray(ops.bgmv(x, a, b, idx))
    np.testing.assert_allclose(y, np.asarray(ref.bgmv_ref(x, a, b, idx)),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_array_equal(y[0], y[1])


def test_bgmv_zero_rank_contributes_zero():
    """A fully-masked adapter (rank 0) must contribute exactly zero."""
    x, a, b, _ = _inputs(4, 128, 128, 2, 8, 0)
    am = a.at[1].set(0.0)
    idx = jnp.array([0, 1, 1, 0], jnp.int32)
    y = np.asarray(ops.bgmv(x, am, b, idx))
    assert np.array_equal(y[1], np.zeros_like(y[1]))
    assert np.array_equal(y[2], np.zeros_like(y[2]))
