"""Batched whole-tree aggregation engine — the server hot path, compiled once.

The seed path (``aggregate_tree``) loops over LoRA targets in Python and
runs one un-jitted ``aggregate_hlora`` per target, which in turn vmaps an
SVD per layer: at RoBERTa-large scale that is 24 layers × T targets of
op-by-op dispatch, re-traced work on every round and every async submit.

This engine does the whole tree in **one jit-compiled call**:

1. *Group* targets by leaf signature — ``(A, B, mask)`` shapes agree for
   e.g. q/k/v at the same width, differ for MLP up/down projections — so
   each group batches cleanly.
2. *Stack* every group into one ``(T·L, K, d_in, r)`` batch (T targets ×
   L layers), the FLoRA-style stacking trick generalized to the tree.
3. Run a **single vmapped pipeline** per group: masked/weighted factor
   stacking → ``svd_factored`` (or a dense ``recon_agg``-Pallas-backed
   reconstruction for ``method="exact"/"randomized"``) → ``split_factors``
   → per-client rank redistribution.
4. *Unstack* back into the original tree layout.

jit's structural cache keys on the tree's shapes/dtypes, so round 2
onwards (and every async submit with the same tree) replays the compiled
executable — zero re-tracing. ``trace_count`` exposes that for tests.

The engine also **surfaces the singular spectrum** it already computed
(per target, per layer), so rank-adaptation policies (``adapt_ranks``)
read Σ directly instead of re-deriving it from factor norms — which was
silently wrong under ``split="sqrt"`` (row norms of B' are √σ there).
"""
from __future__ import annotations

import math
import time
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import svd as svd_lib
from repro.launch import mesh as mesh_lib
from repro.launch import sharding as shard_rules

StackedAdapter = Dict[str, jax.Array]


def _prod(xs) -> int:
    return int(math.prod(xs)) if xs else 1


def rank_for_energy(spectrum, energy: float, r_min: int, r_max: int) -> int:
    """Smallest rank whose leading singular directions capture ``energy``
    of the spectrum's total σ² energy, clamped to [r_min, r_max].

    ``spectrum``: (..., r) singular values — leading axes (layers,
    targets stacked by the caller) are pooled by *mean energy* (σ²,
    then cumulate), which is the seed's pooling order: squaring after
    pooling weights dissimilar spectra differently and shifts the
    cutoff. This is the one place the energy→rank rule lives; both the
    per-client and the per-target policies in ``fed/server.py`` call
    it, so they can never drift apart."""
    s = np.asarray(spectrum, np.float64)
    s2 = np.mean(s.reshape(-1, s.shape[-1]) ** 2, axis=0)
    cum = np.cumsum(s2) / max(float(s2.sum()), 1e-30)
    r = int(np.searchsorted(cum, energy) + 1)
    return int(np.clip(r, r_min, r_max))


# ---------------------------------------------------------------------------
# recon_agg backend autotune (ROADMAP follow-up: pick use_pallas by a timed
# probe, not a backend string check)
# ---------------------------------------------------------------------------

_AUTOTUNE_CACHE: Dict[tuple, bool] = {}
# Off-TPU the Pallas kernel runs in interpret mode (a Python loop over
# grid points); above this element count even the one-shot probe itself
# is not worth running — the einsum always wins.
_INTERPRET_PROBE_LIMIT = 1 << 16


def _probe_recon_backend(kc: int, d_in: int, r: int, d_out: int,
                         dtype) -> bool:
    """One-shot timed autotune for the dense-reconstruction backend:
    run the Pallas ``recon_agg`` and the einsum contraction once each
    (after a compile/warmup call) on representative ones-filled inputs of
    the true shape and keep the faster one. Cached per (shape, dtype)
    for the life of the process."""
    key = (kc, d_in, r, d_out, jnp.dtype(dtype).name)
    hit = _AUTOTUNE_CACHE.get(key)
    if hit is not None:
        return hit
    from repro.kernels import ops, ref
    if not ops.on_tpu() and kc * d_in * d_out > _INTERPRET_PROBE_LIMIT:
        _AUTOTUNE_CACHE[key] = False
        return False
    a = jnp.ones((kc, d_in, r), dtype)
    b = jnp.ones((kc, r, d_out), dtype)
    eta = jnp.ones((kc,), jnp.float32)
    ref_fn = jax.jit(ref.recon_agg_ref)

    def timed(fn) -> float:
        fn(a, b, eta).block_until_ready()      # compile + warm
        # the autotune probe is a genuine one-shot timing measurement:
        # its result picks a backend and is never recorded as an event,
        # so it deliberately bypasses the Recorder clock
        t0 = time.perf_counter()  # repro: allow=clock-discipline (autotune)
        fn(a, b, eta).block_until_ready()
        # repro: allow=clock-discipline (autotune probe)
        return time.perf_counter() - t0

    decision = timed(ops.recon_agg) < timed(ref_fn)
    _AUTOTUNE_CACHE[key] = decision
    return decision


# ---------------------------------------------------------------------------
# Per-batch-item math (one (target, layer) slice; vmapped over the batch).
# All mirror core/aggregate.py exactly — the engine is a *batched* evaluation
# strategy for the same equations, and tests pin the two to 1e-5.
# ---------------------------------------------------------------------------

def _coefficients(mask: jax.Array, eta: jax.Array, alpha: jax.Array
                  ) -> jax.Array:
    """η̂_k · s_k with s_k = alpha / r_eff_k (Eq. 2 coefficient)."""
    etan = eta / jnp.sum(eta)
    r_eff = jnp.maximum(jnp.sum(mask, axis=-1), 1.0)
    return etan * alpha / r_eff


def _masked(a, b, mask):
    return a * mask[:, None, :], b * mask[:, :, None]


def _dense_update(a, b, mask, eta, alpha, *, use_pallas: bool) -> jax.Array:
    """ΔW' = Σ_k coef_k (A_k·m_k)(B_k·m_k) — Eq. 2, dense form."""
    from repro.kernels import ops, ref
    coef = _coefficients(mask, eta, alpha)
    am, bm = _masked(a, b, mask)
    if use_pallas:
        return ops.recon_agg(am, bm, coef)
    return ref.recon_agg_ref(am, bm, coef)


def _factored_update(a, b, mask, eta, alpha) -> Tuple[jax.Array, jax.Array]:
    """ΔW' = P Q without materializing it: P (d_in, K·r), Q (K·r, d_out)."""
    coef = _coefficients(mask, eta, alpha)
    am, bm = _masked(a, b, mask)
    am = am * coef[:, None, None]
    k, d_in, r = am.shape
    p = jnp.transpose(am, (1, 0, 2)).reshape(d_in, k * r)
    q = bm.reshape(k * r, bm.shape[-1])
    return p, q


def _redistribute(a_new, b_new, s, new_mask, alpha):
    """Per-client Eq. 3: mask to r_k, undo the client's forward scale."""
    r_eff = jnp.maximum(jnp.sum(new_mask, axis=-1), 1.0)
    inv_scale = r_eff / alpha
    a_out = a_new[None] * new_mask[:, None, :]
    b_out = b_new[None] * new_mask[:, :, None] * inv_scale[:, None, None]
    return a_out, b_out, s


def _hlora_item(a, b, mask, new_mask, eta, alpha, key, *,
                method: str, split: str, use_pallas: bool,
                factored_impl: str = "gram"):
    """a: (K, d_in, r), b: (K, r, d_out), mask: (K, r), new_mask: (K', r)."""
    r_max = a.shape[-1]
    if method == "factored":
        p, q = _factored_update(a, b, mask, eta, alpha)
        svd_fn = svd_lib.svd_factored_gram if factored_impl == "gram" \
            else svd_lib.svd_factored
        u, s, vt = svd_fn(p, q, r_max)
    elif method == "exact":
        w = _dense_update(a, b, mask, eta, alpha, use_pallas=use_pallas)
        u, s, vt = svd_lib.svd_exact(w, r_max)
    elif method == "randomized":
        w = _dense_update(a, b, mask, eta, alpha, use_pallas=use_pallas)
        u, s, vt = svd_lib.svd_randomized(w, r_max, key)
    else:
        raise ValueError(f"unknown svd method {method!r}")
    a_new, b_new = svd_lib.split_factors(u, s, vt, r_max, split)
    return _redistribute(a_new, b_new, s, new_mask, alpha)


def _naive_item(a, b, mask, new_mask, eta, alpha, key, **_static):
    """Eq. 1 separate averaging (zero-padding baseline). Output matches
    aggregate_naive: Ā/B̄ broadcast over the *input* client axis, the mask
    tree swapped for the redistribution masks. Spectrum is the (biased)
    singular spectrum of Ā·B̄ proxied by zeros — naive has no SVD."""
    del new_mask, alpha, key
    etan = eta / jnp.sum(eta)
    am, bm = _masked(a, b, mask)
    a_bar = jnp.einsum("k,kir->ir", etan, am)
    b_bar = jnp.einsum("k,kro->ro", etan, bm)
    a_out = jnp.broadcast_to(a_bar[None], a.shape)
    b_out = jnp.broadcast_to(b_bar[None], b.shape)
    s = jnp.zeros((a.shape[-1],), a.dtype)
    return a_out, b_out, s


# ---------------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------------

class AggregationEngine:
    """Jit-cached batched tree aggregation.

    One engine instance holds one jit cache per static configuration
    (strategy, method, split, masks-provided, per-shape backend map);
    within a configuration, jax.jit's structural cache keys on the
    adapter tree's names/shapes/dtypes — so repeated rounds (sync) and
    repeated submits (async) replay a compiled executable with zero
    Python-loop dispatch.

    ``use_pallas=None`` (default) resolves the dense-reconstruction
    backend by a one-shot *timed autotune probe* per (shape, dtype) —
    not a backend string check — cached process-wide (see
    ``_probe_recon_backend``). Pass True/False to force.

    Call returns ``(tree, spectra)`` where ``spectra[target]`` is the
    singular spectrum of that target's aggregated ΔW' with shape
    ``(*stack, r_max)`` (zeros under the naive strategy, which runs no
    SVD).
    """

    def __init__(self, use_pallas: Optional[bool] = None,
                 factored_impl: str = "gram", mesh=None):
        """``factored_impl`` selects the method='factored' SVD backend:
        'gram' (default) — CholeskyQR, all-matmul, ~4× faster at server
        scale; 'qr' — LAPACK Householder QR, bit-identical to the seed
        per-target ``svd_factored`` path (used by equivalence tests).

        ``mesh``: an optional device mesh with a 'data' axis. Each shape
        group's (T·L, K, d, r) stacked batch is shard_map'd over the data
        axes — every batch item (one target×layer aggregation) runs
        entirely on one device, so the sharded path evaluates the exact
        same per-item op sequence as the single-device path (equivalence
        pinned in tests). Batches that don't divide the device count are
        tile-padded with leading items (valid data, sliced off after).
        The engine runs on the mesh's Auto-axis view
        (``mesh_lib.auto_axes``), so a mesh with Explicit axes works too."""
        self._jitted: Dict[tuple, callable] = {}
        self.trace_count = 0   # incremented at trace time only
        self.use_pallas = use_pallas
        self.factored_impl = factored_impl
        self.mesh = mesh_lib.auto_axes(mesh)

    # -- public entry -------------------------------------------------------

    def __call__(
        self,
        adapters: Dict[str, StackedAdapter],
        eta: jax.Array,
        alpha: float,
        *,
        strategy: str = "hlora",
        new_masks: Optional[Dict[str, jax.Array]] = None,
        method: str = "factored",
        split: str = "paper",
        key: Optional[jax.Array] = None,
    ) -> Tuple[Dict[str, StackedAdapter], Dict[str, jax.Array]]:
        if strategy not in ("naive", "hlora"):
            raise ValueError(f"unknown strategy {strategy!r}")
        pallas_map = self._resolve_pallas(adapters, strategy, method)
        cfg = (strategy, method, split, new_masks is not None, pallas_map,
               self.factored_impl, self.mesh)
        fn = self._jitted.get(cfg)
        if fn is None:
            fn = jax.jit(partial(self._run, strategy=strategy, method=method,
                                 split=split, pallas_map=pallas_map,
                                 factored_impl=self.factored_impl))
            self._jitted[cfg] = fn
        if key is None:
            key = jax.random.PRNGKey(0)
        alpha_arr = jnp.asarray(alpha, jnp.float32)
        return fn(adapters, new_masks, jnp.asarray(eta), alpha_arr, key)

    def _resolve_pallas(self, adapters, strategy: str, method: str) -> tuple:
        """Per-recon-shape backend decisions as a static, hashable map
        ``((k, d_in, r, d_out) -> bool, ...)``. Explicit ``use_pallas``
        wins; otherwise each distinct shape gets a one-shot timed probe
        (only the dense-reconstruction methods ever run the kernel)."""
        sigs = {}
        for ad in adapters.values():
            sigs[(ad["A"].shape[0], ad["A"].shape[-2],
                  ad["A"].shape[-1], ad["B"].shape[-1])] = ad["A"].dtype
        sigs = dict(sorted(sigs.items()))
        if self.use_pallas is not None:
            return tuple((s, bool(self.use_pallas)) for s in sigs)
        if strategy != "hlora" or method not in ("exact", "randomized"):
            return tuple((s, False) for s in sigs)  # kernel never runs
        return tuple((s, _probe_recon_backend(*s, dt))
                     for s, dt in sigs.items())

    # -- traced body --------------------------------------------------------

    def _run(self, adapters, new_masks, eta, alpha, key, *,
             strategy, method, split, pallas_map, factored_impl):
        self.trace_count += 1   # side effect fires only while tracing
        base_item = _naive_item if strategy == "naive" else _hlora_item
        backend = dict(pallas_map)

        groups: Dict[tuple, list] = {}
        for name in sorted(adapters):
            ad = adapters[name]
            nm = ad["mask"] if new_masks is None else new_masks[name]
            sig = (ad["A"].shape, ad["B"].shape, ad["mask"].shape, nm.shape)
            groups.setdefault(sig, []).append(name)

        out: Dict[str, StackedAdapter] = {}
        spectra: Dict[str, jax.Array] = {}
        for sig, members in sorted(groups.items()):
            a_shape, b_shape = sig[0], sig[1]
            use_pallas = backend[(a_shape[0], a_shape[-2], a_shape[-1],
                                  b_shape[-1])]
            item = partial(base_item, method=method, split=split,
                           use_pallas=use_pallas,
                           factored_impl=factored_impl)
            self._run_group(adapters, new_masks, eta, alpha, key, members,
                            item, out, spectra)
        return out, spectra

    def _run_group(self, adapters, new_masks, eta, alpha, key, members,
                   item, out, spectra):
        # Stack the group: (T, K, *stack, d_in, r) etc.
        a = jnp.stack([adapters[n]["A"] for n in members])
        b = jnp.stack([adapters[n]["B"] for n in members])
        m = jnp.stack([adapters[n]["mask"] for n in members])
        nm = m if new_masks is None else \
            jnp.stack([new_masks[n] for n in members])

        t, k = a.shape[0], a.shape[1]
        stack = a.shape[2:-2]
        d_in, r = a.shape[-2], a.shape[-1]
        d_out = b.shape[-1]
        k_out = nm.shape[1]
        batch = t * _prod(stack)

        def to_batch(x, k_axis_size, *mat):
            # (T, K, *stack, *mat) -> (T·L, K, *mat)
            perm = (0,) + tuple(range(2, 2 + len(stack))) + (1,) + \
                tuple(range(2 + len(stack), x.ndim))
            return jnp.transpose(x, perm).reshape(batch, k_axis_size, *mat)

        ab = to_batch(a, k, d_in, r)
        bb = to_batch(b, k, r, d_out)
        mb = to_batch(m, k, r)
        nmb = to_batch(nm, k_out, r)
        keys = jax.random.split(key, batch)

        a_o, b_o, s = self._dispatch_batch(item, ab, bb, mb, nmb, eta,
                                           alpha, keys, batch)

        def from_batch(x):
            # (T·L, K', *mat) -> (T, K', *stack, *mat)
            y = x.reshape(t, *stack, *x.shape[1:])
            perm = (0, 1 + len(stack)) + tuple(range(1, 1 + len(stack))) + \
                tuple(range(2 + len(stack), y.ndim))
            return jnp.transpose(y, perm)

        a_o, b_o = from_batch(a_o), from_batch(b_o)
        s = s.reshape(t, *stack, r)
        for i, name in enumerate(members):
            mask_out = adapters[name]["mask"] if new_masks is None \
                else new_masks[name]
            out[name] = {"A": a_o[i], "B": b_o[i], "mask": mask_out}
            spectra[name] = s[i]

    def _dispatch_batch(self, item, ab, bb, mb, nmb, eta, alpha, keys,
                        batch: int):
        """Run the vmapped per-item pipeline over the stacked batch —
        locally, or shard_map'd over the mesh's data axes. Items are
        independent (the only cross-item state, eta/alpha, is
        replicated), so sharding needs no collectives: each device runs
        the identical per-item math on its slice of the batch."""
        vmapped = jax.vmap(item, in_axes=(0, 0, 0, 0, None, None, 0))
        ndev = mesh_lib.data_axis_size(self.mesh)
        if ndev <= 1:
            return vmapped(ab, bb, mb, nmb, eta, alpha, keys)
        # Every device holds at least two items whenever the batch has
        # two: XLA drops a size-1 batch dim and may then lower a dot in a
        # different summation order, so a one-item shard would not be
        # bit-identical to the batched single-device program.
        per_dev = max(-(-batch // ndev), min(batch, 2))
        pad = per_dev * ndev - batch
        if pad:
            # Tile-pad with leading items: real data (zero-padding would
            # push rank-0 garbage through Cholesky), sliced off below.
            sel = jnp.arange(pad) % batch

            def tile(x):
                return jnp.concatenate([x, jnp.take(x, sel, axis=0)])

            ab, bb, mb, nmb, keys = map(tile, (ab, bb, mb, nmb, keys))

        axes = shard_rules.data_shard_axes(self.mesh)

        def bspec(x):
            return P(axes, *((None,) * (x.ndim - 1)))

        def rspec(x):
            return P(*((None,) * jnp.ndim(x)))

        a_sh = jax.eval_shape(vmapped, ab, bb, mb, nmb, eta, alpha, keys)
        fn = jax.shard_map(
            vmapped, mesh=self.mesh,
            in_specs=(bspec(ab), bspec(bb), bspec(mb), bspec(nmb),
                      rspec(eta), rspec(alpha), bspec(keys)),
            out_specs=jax.tree.map(bspec, a_sh),
            # eigh/cholesky custom calls carry no replication rule
            check_vma=False)
        a_o, b_o, s = fn(ab, bb, mb, nmb, eta, alpha, keys)
        if pad:
            a_o, b_o, s = a_o[:batch], b_o[:batch], s[:batch]
        return a_o, b_o, s

    # -- introspection ------------------------------------------------------

    def cache_size(self) -> int:
        """Number of distinct static configurations compiled so far."""
        return len(self._jitted)


# Module-level default engine: servers/benchmarks share one jit cache.
_default_engine: Optional[AggregationEngine] = None


def default_engine() -> AggregationEngine:
    global _default_engine
    if _default_engine is None:
        _default_engine = AggregationEngine()
    return _default_engine
