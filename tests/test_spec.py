"""Speculative decoding subsystem tests: losslessness, rollback, and the
multi-token verify kernel.

The load-bearing property is *exactness*: draft–verify greedy decode
must be byte-identical to plain paged decode (and hence to the
merged-weight oracle) for ANY drafter — acceptance quality moves the
speedup, never the tokens.  The tests pin that across the acceptance
extremes (forced-accept / forced-reject scripted drafters), the real
drafters (self-draft layer subset, n-gram lookup), spec window sizes,
prefill chunk sizes, and page-pool pressure (deferral + preemption +
rollback all interleaved), with trace counts flat throughout.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import get_reduced
from repro.kernels import ops, ref
from repro.serve import (AdapterRegistry, NGramDrafter, ScriptedDrafter,
                         SelfDrafter, ServeEngine)
from repro.serve.oracle import (greedy_continuations, make_demo_adapter,
                                merged_greedy)

KEY = jax.random.PRNGKey(0)
RANKS = (2, 4, 6, 8)
PROMPT_LEN = 6
STEPS = 10


@pytest.fixture(scope="module")
def setup():
    cfg = get_reduced("gemma-2b")
    key = jax.random.PRNGKey(0)
    from repro.models import model as model_lib
    params = model_lib.init_params(key, cfg)
    adapters = {
        f"client{i}": make_demo_adapter(jax.random.fold_in(key, 100 + i),
                                        cfg, r)
        for i, r in enumerate(RANKS)}
    prompts = np.asarray(jax.random.randint(
        jax.random.fold_in(key, 3), (8, PROMPT_LEN), 3, cfg.vocab_size))
    oracle = greedy_continuations(
        params, cfg, prompts,
        [adapters[f"client{i % len(RANKS)}"] for i in range(8)], STEPS)
    return cfg, params, adapters, prompts, oracle


def _registry(cfg, adapters):
    reg = AdapterRegistry(cfg, capacity=len(adapters))
    for aid, tree in adapters.items():
        reg.register(aid, tree)
    return reg


def _run_spec(cfg, params, adapters, prompts, drafter, *, n=8, spec_k=4,
              steps=STEPS, scripts=None, **engine_kw):
    engine = ServeEngine(params, cfg, _registry(cfg, adapters),
                         max_batch=n, max_seq=prompts.shape[1] + steps,
                         drafter=drafter, spec_k=spec_k, **engine_kw)
    uids = [engine.submit(prompts[i], f"client{i % len(RANKS)}",
                          max_new_tokens=steps) for i in range(n)]
    if scripts is not None:
        for u, s in zip(uids, scripts):
            drafter.set(u, s)
    outs = engine.run()
    return engine, [outs[u] for u in uids]


# ---------------------------------------------------------------------------
# Losslessness across the acceptance extremes and the real drafters
# ---------------------------------------------------------------------------

def test_forced_accept_is_exact_and_amortizes_dispatches(setup):
    """Acceptance 1 (drafter scripts the true continuation): every
    dispatch commits spec_k + 1 tokens, outputs stay byte-identical to
    the merged oracle over 8 heterogeneous-rank requests, and nothing
    retraces after the first dispatch."""
    cfg, params, adapters, prompts, oracle = setup
    engine, outs = _run_spec(cfg, params, adapters, prompts,
                             ScriptedDrafter(), scripts=oracle)
    for got, want in zip(outs, oracle):
        np.testing.assert_array_equal(got, want)
    stats = engine.spec_stats()
    assert stats["acceptance_rate"] == 1.0
    # prefill commits 1 token; the remaining 9 land in ceil(9/5) = 2
    # verify dispatches instead of 9 decode steps
    assert engine.spec_dispatches == 2
    assert engine.trace_count == 2          # prefill + verify, no decode
    engine.kv.allocator.check()
    assert engine.kv.allocator.free_count == engine.kv.num_pages


def test_forced_reject_is_exact_and_rolls_back(setup):
    """Acceptance 0 (scripts shifted off the true continuation): every
    draft is rejected, decode degenerates to one committed token per
    dispatch, rollback returns the speculatively-extended pages — and
    the output is still byte-identical."""
    cfg, params, adapters, prompts, oracle = setup
    scripts = [(w + 1) % cfg.vocab_size for w in oracle]
    engine, outs = _run_spec(cfg, params, adapters, prompts,
                             ScriptedDrafter(), scripts=scripts)
    for got, want in zip(outs, oracle):
        np.testing.assert_array_equal(got, want)
    stats = engine.spec_stats()
    assert stats["acceptance_rate"] == 0.0
    assert engine.spec_dispatches == STEPS - 1   # one token per dispatch
    assert engine.rollback_pages > 0             # rollback actually fired
    assert engine.trace_count == 2
    engine.kv.allocator.check()
    assert engine.kv.allocator.free_count == engine.kv.num_pages


def test_self_drafter_is_exact_whatever_it_accepts(setup):
    """The shallow layer-subset self-draft shares the paged pool with
    the verify step; whatever its acceptance, tokens must not change.
    Its own jitted step traces exactly once."""
    cfg, params, adapters, prompts, oracle = setup
    engine, outs = _run_spec(cfg, params, adapters, prompts,
                             SelfDrafter(draft_layers=1), spec_k=3)
    for got, want in zip(outs, oracle):
        np.testing.assert_array_equal(got, want)
    assert engine.trace_count == 3          # prefill + verify + draft
    assert engine.drafted_tokens > 0
    engine.kv.allocator.check()


def test_ngram_drafter_is_exact_and_accepts_on_repetitive_prompts(setup):
    """Prompt-lookup drafting on period-4 prompts: positive acceptance
    (the continuation of a repeated phrase is guessable), same tokens."""
    cfg, params, adapters, prompts, _ = setup
    rep = np.tile(prompts[:, :4], (1, 2))
    oracle = [merged_greedy(params, cfg, rep[i],
                            adapters[f"client{i % len(RANKS)}"], STEPS)
              for i in range(4)]
    engine, outs = _run_spec(cfg, params, adapters, rep,
                             NGramDrafter(2), n=4)
    for got, want in zip(outs, oracle):
        np.testing.assert_array_equal(got, want)
    assert engine.accepted_tokens > 0
    assert engine.spec_dispatches < 4 * (STEPS - 1)


def test_spec_window_and_chunk_size_do_not_change_tokens(setup):
    """spec_k and prefill_chunk are evaluation strategy, not semantics."""
    cfg, params, adapters, prompts, oracle = setup
    for spec_k in (1, 3, 5):
        for chunk in (3, 16):
            engine, outs = _run_spec(
                cfg, params, adapters, prompts, ScriptedDrafter(), n=4,
                spec_k=spec_k, scripts=oracle, prefill_chunk=chunk)
            for got, want in zip(outs, oracle[:4]):
                np.testing.assert_array_equal(got, want)


def test_spec_under_page_pressure_with_preemption(setup):
    """A pool far smaller than the traffic: admission defers, extension
    preempts, speculative windows roll back — all interleaved — and
    every request still finishes byte-identical with the pool conserved
    and traces flat."""
    cfg, params, adapters, prompts, oracle = setup
    engine, outs = _run_spec(cfg, params, adapters, prompts,
                             ScriptedDrafter(), scripts=oracle,
                             page_size=4, num_pages=10, prefill_chunk=4)
    for got, want in zip(outs, oracle):
        np.testing.assert_array_equal(got, want)
    assert engine.deferrals > 0
    assert engine.trace_count == 2
    engine.kv.allocator.check()
    assert engine.kv.allocator.free_count == engine.kv.num_pages


def test_spec_interleaves_with_plain_admission_traffic(setup):
    """Requests of wildly different lengths arriving through a 2-row
    batch: rows finish, recycle, re-admit mid-speculation; outputs match
    the per-request oracle."""
    cfg, params, adapters, prompts, _ = setup
    lens = [3, 7, 5, 10, 4]
    drafter = NGramDrafter(2)
    engine = ServeEngine(params, cfg, _registry(cfg, adapters),
                         max_batch=2, max_seq=PROMPT_LEN + STEPS,
                         drafter=drafter, spec_k=3)
    uids = [engine.submit(prompts[i], f"client{i % len(RANKS)}",
                          max_new_tokens=lens[i]) for i in range(5)]
    outs = engine.run()
    for i, uid in enumerate(uids):
        want = merged_greedy(params, cfg, prompts[i],
                             adapters[f"client{i % len(RANKS)}"], lens[i])
        np.testing.assert_array_equal(outs[uid], want)
    engine.kv.allocator.check()


def test_spec_pallas_kernels_interpret(setup):
    """The TPU code path end-to-end: BGMV + multi-token verify kernel +
    flash chunked prefill, all in interpret mode — same greedy tokens as
    the merged oracle."""
    cfg, params, adapters, prompts, oracle = setup
    engine, outs = _run_spec(cfg, params, adapters, prompts,
                             ScriptedDrafter(), n=2, scripts=oracle,
                             prefill_chunk=4, use_pallas=True)
    for got, want in zip(outs, oracle[:2]):
        np.testing.assert_array_equal(got, want)


def test_drafter_requires_paged_mode(setup):
    """The page pool is the engine's one KV cache: any other ``kv_mode``
    is refused, with a drafter or without."""
    cfg, params, adapters, _, _ = setup
    for kv_mode in ("dense", "ring", ""):
        for drafter in (NGramDrafter(), None):
            with pytest.raises(ValueError, match="paged"):
                ServeEngine(params, cfg, _registry(cfg, adapters),
                            kv_mode=kv_mode, drafter=drafter)


# ---------------------------------------------------------------------------
# Multi-token verify kernel vs gather oracle
# ---------------------------------------------------------------------------

def _verify_inputs(bsz, sq, h, hkv, dh, num_pages, ps, p, seed):
    ks = jax.random.split(jax.random.fold_in(KEY, seed), 4)
    q = jax.random.normal(ks[0], (bsz, sq, h, dh))
    kp = jax.random.normal(ks[1], (num_pages + 1, ps, hkv, dh))
    vp = jax.random.normal(ks[2], (num_pages + 1, ps, hkv, dh))
    rng = np.random.RandomState(seed)
    tables = jnp.asarray(rng.permutation(num_pages)[:bsz * p]
                         .reshape(bsz, p), jnp.int32)
    offs = jnp.asarray(rng.randint(0, p * ps - sq + 1, bsz), jnp.int32)
    lens = offs + sq
    lens = lens.at[0].set(0)         # one inactive row
    return q, kp, vp, tables, lens, offs


@settings(max_examples=6, deadline=None)
@given(sq=st.sampled_from([1, 2, 5]),
       dh=st.sampled_from([16, 32, 100]),
       hkv=st.sampled_from([1, 2]),
       groups=st.sampled_from([1, 4]),
       seed=st.integers(min_value=0, max_value=10_000))
def test_verify_kernel_matches_oracle(sq, dh, hkv, groups, seed):
    """Ragged offsets/lengths, GQA grouping, unaligned head dims: the
    padded kernel path equals the gather oracle everywhere."""
    q, kp, vp, tables, lens, offs = _verify_inputs(
        3, sq, hkv * groups, hkv, dh, 16, 8, 4, seed)
    got = ops.paged_verify_attention(q, kp, vp, tables, lens, offs,
                                     page_size=8, interpret=True)
    want = ref.paged_verify_ref(q, kp, vp, tables, lens, offs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_verify_kernel_sq1_equals_decode_kernel():
    """Sq = 1 with q_offsets = lengths - 1 reproduces the decode kernel
    bit-for-bit — the multi-token read is a true generalization."""
    q, kp, vp, tables, lens, _ = _verify_inputs(4, 1, 4, 2, 32, 16, 8, 4,
                                                7)
    offs = jnp.maximum(lens - 1, 0)
    dec = ops.paged_attention(q[:, 0], kp, vp, tables, lens, page_size=8,
                              interpret=True)
    ver = ops.paged_verify_attention(q, kp, vp, tables, lens, offs,
                                     page_size=8, interpret=True)[:, 0]
    np.testing.assert_array_equal(np.asarray(dec), np.asarray(ver))


def test_verify_causality_within_the_draft_window():
    """Corrupting KV at position q_offsets[b] + i must not change any
    output before position i — the in-window mask really is causal."""
    sq = 4
    q, kp, vp, tables, lens, offs = _verify_inputs(2, sq, 2, 2, 32, 12, 8,
                                                   3, 11)
    lens = offs + sq                 # both rows active here
    base = np.asarray(ref.paged_verify_ref(q, kp, vp, tables, lens, offs))
    b, i = 1, 2
    pos = int(offs[b]) + i
    page = int(tables[b, pos // 8])
    kp2 = kp.at[page, pos % 8].set(99.0)
    vp2 = vp.at[page, pos % 8].set(99.0)
    got = np.asarray(ref.paged_verify_ref(q, kp2, vp2, tables, lens, offs))
    kern = np.asarray(ops.paged_verify_attention(
        q, kp2, vp2, tables, lens, offs, page_size=8, interpret=True))
    np.testing.assert_array_equal(got[b, :i], base[b, :i])  # untouched
    assert not np.allclose(got[b, i:], base[b, i:])          # touched
    np.testing.assert_allclose(kern, got, rtol=2e-4, atol=2e-4)
