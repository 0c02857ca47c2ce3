"""End-to-end serving smoke: the tier-1 guard for repro/serve.

Drives the real engine on the reduced gemma config — batched
heterogeneous-rank multi-LoRA decode vs the per-request merged-weight
oracle, continuous batching with row recycling, and retrace-free
hot-swap.

The engine defaults to the paged KV cache with chunked prefill
(PR 3), so these tests pin that path; the retained dense ring cache is
covered explicitly (``kv_mode="dense"``), including the wrap-instead-
of-corrupt regression. A paged engine traces exactly twice: once for
the chunked-prefill step, once for the decode step.
"""
import jax
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.configs.base import LoRAConfig
from repro.models import model as model_lib
from repro.serve import AdapterRegistry, ServeEngine
from repro.serve.oracle import make_demo_adapter, merged_greedy

RANKS = (2, 4, 6, 8)
PROMPT_LEN = 6
STEPS = 10
PAGED_TRACES = 2   # one prefill trace + one decode trace


@pytest.fixture(scope="module")
def setup():
    cfg = get_reduced("gemma-2b")
    key = jax.random.PRNGKey(0)
    params = model_lib.init_params(key, cfg)
    adapters = {
        f"client{i}": make_demo_adapter(jax.random.fold_in(key, 100 + i),
                                        cfg, r)
        for i, r in enumerate(RANKS)}
    prompts = np.asarray(jax.random.randint(
        jax.random.fold_in(key, 3), (8, PROMPT_LEN), 3, cfg.vocab_size))
    return cfg, params, adapters, prompts


def _registry(cfg, adapters):
    reg = AdapterRegistry(cfg, capacity=len(adapters))
    for aid, tree in adapters.items():
        reg.register(aid, tree)
    return reg


def test_batched_heterogeneous_decode_matches_merged_oracle(setup):
    """8 concurrent requests across 4 distinct heterogeneous-rank adapters
    -> greedy tokens identical to per-request merged-weight decoding."""
    cfg, params, adapters, prompts = setup
    engine = ServeEngine(params, cfg, _registry(cfg, adapters),
                         max_batch=8, max_seq=PROMPT_LEN + STEPS)
    uids = [engine.submit(prompts[i], f"client{i % len(RANKS)}",
                          max_new_tokens=STEPS) for i in range(8)]
    outs = engine.run()
    assert engine.trace_count == PAGED_TRACES
    for i, uid in enumerate(uids):
        want = merged_greedy(params, cfg, prompts[i],
                             adapters[f"client{i % len(RANKS)}"], STEPS)
        np.testing.assert_array_equal(outs[uid], want)


def test_mlp_lora_targets_match_merged_oracle(setup):
    """The engine's MLP adapter path (w1/w2/w3 targets) against the same
    merged-weight oracle — attention-only coverage would miss it."""
    cfg, _, _, prompts = setup
    cfg = cfg.with_(lora=LoRAConfig(targets=("q", "v", "w1", "w2", "w3"),
                                    r_max=8))
    key = jax.random.PRNGKey(1)
    params = model_lib.init_params(key, cfg)
    adapters = {f"m{i}": make_demo_adapter(jax.random.fold_in(key, 10 + i),
                                           cfg, r)
                for i, r in enumerate((3, 8))}
    engine = ServeEngine(params, cfg, _registry(cfg, adapters),
                         max_batch=4, max_seq=PROMPT_LEN + STEPS)
    uids = [engine.submit(prompts[i], f"m{i % 2}", max_new_tokens=STEPS)
            for i in range(4)]
    outs = engine.run()
    for i, uid in enumerate(uids):
        want = merged_greedy(params, cfg, prompts[i],
                             adapters[f"m{i % 2}"], STEPS)
        np.testing.assert_array_equal(outs[uid], want)


def test_continuous_batching_recycles_rows(setup):
    """More requests than rows, uneven lengths: finished rows are recycled
    for queued requests, outputs stay correct, nothing retraces."""
    cfg, params, adapters, prompts = setup
    engine = ServeEngine(params, cfg, _registry(cfg, adapters),
                         max_batch=2, max_seq=PROMPT_LEN + STEPS)
    lens = [3, 7, 5, 10, 4]
    uids = [engine.submit(prompts[i], f"client{i % len(RANKS)}",
                          max_new_tokens=lens[i]) for i in range(5)]
    outs = engine.run()
    assert engine.trace_count == PAGED_TRACES
    for i, uid in enumerate(uids):
        want = merged_greedy(params, cfg, prompts[i],
                             adapters[f"client{i % len(RANKS)}"], lens[i])
        np.testing.assert_array_equal(outs[uid], want)


def test_hot_swap_changes_output_without_retrace(setup):
    cfg, params, adapters, prompts = setup
    reg = _registry(cfg, adapters)
    engine = ServeEngine(params, cfg, reg, max_batch=2,
                         max_seq=PROMPT_LEN + STEPS)
    uid = engine.submit(prompts[0], "client3", max_new_tokens=STEPS)
    before = engine.run()[uid]
    traces = engine.trace_count

    swapped = {t: dict(ad, B=ad["B"] + 0.05) for t, ad
               in adapters["client3"].items()}
    reg.register("client3", swapped)
    reg.refresh("client3")
    uid2 = engine.submit(prompts[0], "client3", max_new_tokens=STEPS)
    after = engine.run()[uid2]

    assert engine.trace_count == traces          # zero recompilation
    want = merged_greedy(params, cfg, prompts[0], swapped, STEPS)
    np.testing.assert_array_equal(after, want)   # swap took effect
    assert not np.array_equal(before, after)


def test_requests_are_isolated(setup):
    """A row's tokens don't depend on what else is in the batch: serve the
    same request alone and packed with 7 strangers."""
    cfg, params, adapters, prompts = setup
    reg = _registry(cfg, adapters)
    engine = ServeEngine(params, cfg, reg, max_batch=8,
                         max_seq=PROMPT_LEN + STEPS)
    uid_alone = engine.submit(prompts[0], "client0", max_new_tokens=STEPS)
    alone = engine.run()[uid_alone]
    uids = [engine.submit(prompts[i], f"client{i % len(RANKS)}",
                          max_new_tokens=STEPS) for i in range(8)]
    packed = engine.run()
    np.testing.assert_array_equal(packed[uids[0]], alone)


def test_more_adapters_than_slots_defers_admission(setup):
    """Registry smaller than the working set: requests whose adapter
    cannot be pinned wait in the queue instead of crashing the loop, and
    every request still finishes correctly once slots free up."""
    cfg, params, adapters, prompts = setup
    reg = AdapterRegistry(cfg, capacity=2)
    for aid, tree in adapters.items():
        reg.register(aid, tree)
    engine = ServeEngine(params, cfg, reg, max_batch=4,
                         max_seq=PROMPT_LEN + STEPS)
    uids = [engine.submit(prompts[i], f"client{i}", max_new_tokens=4)
            for i in range(4)]
    outs = engine.run()
    assert reg.evictions >= 1
    for i, uid in enumerate(uids):
        want = merged_greedy(params, cfg, prompts[i],
                             adapters[f"client{i}"], 4)
        np.testing.assert_array_equal(outs[uid], want)


def test_submit_rejections(setup):
    cfg, params, adapters, _ = setup
    engine = ServeEngine(params, cfg, _registry(cfg, adapters),
                         max_batch=2, max_seq=8)
    with pytest.raises(ValueError):
        engine.submit(np.arange(5, dtype=np.int32), "client0",
                      max_new_tokens=8)
    with pytest.raises(KeyError):
        engine.submit(np.arange(2, dtype=np.int32), "nobody",
                      max_new_tokens=2)


# ---------------------------------------------------------------------------
# Paged KV specifics
# ---------------------------------------------------------------------------

def test_paged_matches_dense_and_oracle(setup):
    """The paged engine, the dense fallback, and the merged-weight oracle
    all agree token-for-token on the same traffic."""
    cfg, params, adapters, prompts = setup
    outs = {}
    for mode in ("paged", "dense"):
        engine = ServeEngine(params, cfg, _registry(cfg, adapters),
                             max_batch=4, max_seq=PROMPT_LEN + STEPS,
                             kv_mode=mode, page_size=4, prefill_chunk=4)
        uids = [engine.submit(prompts[i], f"client{i % len(RANKS)}",
                              max_new_tokens=STEPS) for i in range(4)]
        done = engine.run()
        outs[mode] = [done[u] for u in uids]
    for i in range(4):
        want = merged_greedy(params, cfg, prompts[i],
                             adapters[f"client{i % len(RANKS)}"], STEPS)
        np.testing.assert_array_equal(outs["paged"][i], want)
        np.testing.assert_array_equal(outs["dense"][i], want)


def test_paged_oversubscription_defers_and_preempts(setup):
    """A pool with fewer pages than the traffic needs: admission defers,
    decode-time extension preempts, and every request still finishes
    with oracle-exact tokens — with zero retraces throughout."""
    cfg, params, adapters, prompts = setup
    engine = ServeEngine(params, cfg, _registry(cfg, adapters),
                         max_batch=8, max_seq=PROMPT_LEN + STEPS,
                         page_size=4, num_pages=10, prefill_chunk=4)
    uids = [engine.submit(prompts[i], f"client{i % len(RANKS)}",
                          max_new_tokens=STEPS) for i in range(8)]
    outs = engine.run()
    assert engine.deferrals > 0          # pool was actually oversubscribed
    assert engine.trace_count == PAGED_TRACES
    engine.kv.allocator.check()          # no page leaked or double-owned
    assert engine.kv.allocator.free_count == engine.kv.num_pages
    for i, uid in enumerate(uids):
        want = merged_greedy(params, cfg, prompts[i],
                             adapters[f"client{i % len(RANKS)}"], STEPS)
        np.testing.assert_array_equal(outs[uid], want)


def test_paged_admits_beyond_dense_bound(setup):
    """The page pool admits concurrent traffic a dense cache of the same
    memory could not: 4 short requests through a pool whose bytes equal
    a 2-row dense cache."""
    cfg, params, adapters, prompts = setup
    # dense: 2 rows x 16 slots; paged: pool of 8 pages x 4 slots = same
    # token capacity, but spread over 4 concurrent rows.
    engine = ServeEngine(params, cfg, _registry(cfg, adapters),
                         max_batch=4, max_seq=16, page_size=4, num_pages=8,
                         prefill_chunk=4)
    uids = [engine.submit(prompts[i][:4], f"client{i}", max_new_tokens=4)
            for i in range(4)]   # 8 tokens each = 2 pages each
    outs = engine.run()
    assert set(outs) == set(uids)
    assert engine.deferrals == 0         # all 4 admitted concurrently
    for i, uid in enumerate(uids):
        want = merged_greedy(params, cfg, prompts[i][:4],
                             adapters[f"client{i}"], 4)
        np.testing.assert_array_equal(outs[uid], want)


def test_paged_trace_flat_across_page_extensions(setup):
    """Crossing page boundaries (1-token prompt growing 12 tokens across
    3 pages) extends the row's page list without retracing."""
    cfg, params, adapters, prompts = setup
    engine = ServeEngine(params, cfg, _registry(cfg, adapters),
                         max_batch=2, max_seq=16, page_size=4,
                         prefill_chunk=4)
    uid = engine.submit(prompts[0][:2], "client0", max_new_tokens=12)
    outs = engine.run()
    assert engine.trace_count == PAGED_TRACES
    want = merged_greedy(params, cfg, prompts[0][:2], adapters["client0"],
                         12)
    np.testing.assert_array_equal(outs[uid], want)


def test_prefill_chunk_size_does_not_change_tokens(setup):
    """Chunked prefill is an evaluation strategy, not a semantic change:
    any chunk size produces identical greedy tokens."""
    cfg, params, adapters, prompts = setup
    ref_out = None
    for chunk in (1, 3, 4, 16):
        engine = ServeEngine(params, cfg, _registry(cfg, adapters),
                             max_batch=2, max_seq=PROMPT_LEN + STEPS,
                             page_size=4, prefill_chunk=chunk)
        uid = engine.submit(prompts[1], "client1", max_new_tokens=STEPS)
        out = engine.run()[uid]
        if ref_out is None:
            ref_out = out
        else:
            np.testing.assert_array_equal(out, ref_out)
    want = merged_greedy(params, cfg, prompts[1], adapters["client1"],
                         STEPS)
    np.testing.assert_array_equal(ref_out, want)


def test_paged_engine_pallas_kernels_interpret(setup):
    """The TPU code path end-to-end (BGMV + paged_attn decode + flash
    chunked prefill, all in interpret mode): same greedy tokens as the
    merged oracle, including a pool capacity that is not a multiple of
    the flash block size."""
    cfg, params, adapters, prompts = setup
    # 33 pages x 8 slots = 264-token row capacity: NOT a multiple of the
    # 256 default flash block — the prefill path must pick a dividing
    # block size instead of tripping the kernel's tiling assert.
    engine = ServeEngine(params, cfg, _registry(cfg, adapters),
                         max_batch=2, max_seq=264,
                         page_size=8, prefill_chunk=4, use_pallas=True)
    uid = engine.submit(prompts[2], "client2", max_new_tokens=3)
    outs = engine.run()
    want = merged_greedy(params, cfg, prompts[2], adapters["client2"], 3)
    np.testing.assert_array_equal(outs[uid], want)


def test_rows_grouped_by_adapter_slot(setup):
    """Paged dispatches sort batch rows by adapter slot before the BGMV
    gather (the SGMV precondition) — a host-side permutation, so greedy
    tokens are unchanged and the distinct-slot count is surfaced."""
    cfg, params, adapters, prompts = setup
    engine = ServeEngine(params, cfg, _registry(cfg, adapters),
                         max_batch=8, max_seq=PROMPT_LEN + STEPS)
    uids = [engine.submit(prompts[i], f"client{i % len(RANKS)}",
                          max_new_tokens=STEPS) for i in range(8)]
    outs = engine.run()
    # equal-length requests: the last decode dispatch still had all 8
    # rows active across the 4 distinct adapters
    assert engine.bgmv_groups == len(RANKS)
    assert engine.trace_count == PAGED_TRACES    # sorting never retraces
    for i, uid in enumerate(uids):
        want = merged_greedy(params, cfg, prompts[i],
                             adapters[f"client{i % len(RANKS)}"], STEPS)
        np.testing.assert_array_equal(outs[uid], want)


# 3 rows of at most 4 four-slot pages (12 table entries): a pool smaller
# than the tables and one of 30 pages, more than twice their size; decode
# reads either in place, trash page included
@pytest.mark.parametrize("num_pages,kv_slots", [(8, (8 + 1) * 4),
                                                (30, (30 + 1) * 4)],
                         ids=["pool_direct", "large_pool"])
def test_paged_decode_paths_match_dense_under_churn(setup, num_pages,
                                                    kv_slots):
    """Ragged requests through 3 rows: rows recycle, pages are freed and
    re-allocated with stale KV in them (the 8-page pool also preempts a
    row, which replays). Greedy tokens equal the dense-cache engine's
    at either pool size; ``decode_kv_slots`` counts the pool's slots."""
    cfg, params, adapters, prompts = setup
    mix = [(6, 10), (3, 4), (5, 7), (2, 12), (6, 3), (4, 9), (1, 8)]
    outs = {}
    for mode in ("paged", "dense"):
        extra = ({"page_size": 4, "num_pages": num_pages}
                 if mode == "paged" else {})
        engine = ServeEngine(params, cfg, _registry(cfg, adapters),
                             max_batch=3, max_seq=16, kv_mode=mode,
                             prefill_chunk=4, **extra)
        uids = [engine.submit(prompts[i][:n], f"client{i % len(RANKS)}",
                              max_new_tokens=m)
                for i, (n, m) in enumerate(mix)]
        done = engine.run()
        outs[mode] = [done[u] for u in uids]
        if mode == "paged":
            assert engine.metrics.gauge(
                "serve.decode_kv_slots").value == kv_slots
            engine.kv.allocator.check()
    for got, want in zip(outs["paged"], outs["dense"]):
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("use_pallas,kv_slots", [(False, 257 * 16),
                                                 (True, 8 * 128 * 16)],
                         ids=["plain", "pallas"])
def test_decode_kv_slots_at_phi3_serving_shape(setup, use_pallas,
                                               kv_slots):
    """The engine shape of the Phi-3-mini serving benchmark (8 rows of
    2048 tokens, 256 pages of 16): the plain path reads the 257-page
    pool in place, the Pallas kernel the 8 x 128 table entries."""
    cfg, params, adapters, _ = setup
    engine = ServeEngine(params, cfg, _registry(cfg, adapters),
                         max_batch=8, max_seq=2048, page_size=16,
                         num_pages=256, use_pallas=use_pallas)
    assert engine.decode_kv_slots == kv_slots


# ---------------------------------------------------------------------------
# Dense-ring fallback regression (the PR-3 satellite bugfix)
# ---------------------------------------------------------------------------

def test_dense_ring_overflow_raises_not_corrupts(setup):
    """A row driven past its ring must fail loudly. The seed engine
    silently wrapped ``pos % slots``, overwriting the oldest live slots
    while the validity mask still reported them current."""
    cfg, params, adapters, prompts = setup
    engine = ServeEngine(params, cfg, _registry(cfg, adapters),
                         max_batch=1, max_seq=8, kv_mode="dense")
    uid = engine.submit(prompts[0][:4], "client0", max_new_tokens=4)
    # bypass submit's guard, as a scheduler bug or future code path might
    engine._queue[0]["max_new"] = 10
    with pytest.raises(RuntimeError, match="ring"):
        engine.run()
    del uid


def test_dense_insert_drops_out_of_range_writes():
    """The traced insert itself fails safe: an out-of-range position
    leaves the cache bit-identical instead of wrapping onto slot 0."""
    from repro.serve.engine import _cache_insert_rows
    lc = {"k": jax.numpy.ones((2, 4, 1, 8)),
          "v": jax.numpy.ones((2, 4, 1, 8)),
          "pos": jax.numpy.zeros((2, 4), jax.numpy.int32)}
    k_new = jax.numpy.full((2, 1, 1, 8), 7.0)
    out = _cache_insert_rows(lc, k_new, k_new,
                             jax.numpy.asarray([5, 9], jax.numpy.int32))
    np.testing.assert_array_equal(np.asarray(out["k"]), np.asarray(lc["k"]))
    np.testing.assert_array_equal(np.asarray(out["pos"]),
                                  np.asarray(lc["pos"]))
