"""Paged-KV subsystem tests: allocator invariants + kernel equivalence.

The allocator invariants under test are the ones the serving scheduler
leans on: conservation (every page free or owned by exactly one owner),
no double-use, failed alloc/extend leave state untouched, pinned owners
never surface as preemption victims.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels import ops, ref
from repro.serve.pages import PageAllocator, PagedKV

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# PageAllocator
# ---------------------------------------------------------------------------

@settings(max_examples=8, deadline=None)
@given(num_pages=st.integers(min_value=1, max_value=24),
       seed=st.integers(min_value=0, max_value=10_000))
def test_allocator_random_walk_conserves_pages(num_pages, seed):
    """A random alloc/extend/truncate/free walk never loses or
    duplicates a page, and every failure leaves the allocator
    bit-identical."""
    rng = np.random.RandomState(seed)
    alloc = PageAllocator(num_pages)
    live = set()
    for step in range(60):
        op = rng.randint(4)
        if op == 3 and live:
            # speculative rollback: keep a random prefix, the freed
            # suffix must land back in the free list
            owner = sorted(live)[rng.randint(len(live))]
            held = list(alloc.pages_of(owner))
            keep = int(rng.randint(0, len(held) + 2))
            before_free = alloc.free_count
            freed = alloc.truncate(owner, keep)
            assert alloc.pages_of(owner) == held[:keep]
            assert freed == held[keep:]
            assert alloc.free_count == before_free + len(freed)
            assert owner in alloc.owners()        # rollback != teardown
        elif op == 0:
            owner = f"o{step}"
            n = int(rng.randint(0, num_pages + 2))
            before = alloc.free_count
            got = alloc.alloc(owner, n)
            if n > before:
                assert got is None and alloc.free_count == before
            else:
                assert got is not None and len(got) == n
                assert len(set(got)) == n          # distinct pages
                live.add(owner)
        elif op == 1 and live:
            owner = sorted(live)[rng.randint(len(live))]
            before = alloc.free_count
            held = list(alloc.pages_of(owner))
            got = alloc.extend(owner, 1)
            if before == 0:
                assert got is None
                assert alloc.pages_of(owner) == held
            else:
                assert alloc.pages_of(owner) == held + got
        elif op == 2 and live:
            owner = sorted(live)[rng.randint(len(live))]
            held = len(alloc.pages_of(owner))
            freed = alloc.free(owner)
            assert len(freed) == held
            live.discard(owner)
        alloc.check()   # conservation after every operation
    # ownership is disjoint
    owned = [p for o in alloc.owners() for p in alloc.pages_of(o)]
    assert len(owned) == len(set(owned))


@settings(max_examples=8, deadline=None)
@given(num_pages=st.integers(min_value=2, max_value=16),
       npinned=st.integers(min_value=0, max_value=4),
       seed=st.integers(min_value=0, max_value=10_000))
def test_allocator_pinned_never_victimized(num_pages, npinned, seed):
    """victims() must not offer a pinned owner, and must return None
    rather than an insufficient set."""
    rng = np.random.RandomState(seed)
    alloc = PageAllocator(num_pages)
    owners = []
    while alloc.free_count > 0:
        o = f"o{len(owners)}"
        alloc.alloc(o, int(rng.randint(1, alloc.free_count + 1)))
        owners.append(o)
    pinned = owners[:npinned]
    for o in pinned:
        alloc.pin(o)
    unpinned_pages = sum(len(alloc.pages_of(o)) for o in owners
                         if o not in pinned)
    for need in (1, unpinned_pages, unpinned_pages + 1):
        victims = alloc.victims(need)
        if need <= unpinned_pages:
            assert victims is not None
            assert not set(victims) & set(pinned)
            covered = sum(len(alloc.pages_of(v)) for v in victims)
            assert covered >= need
        else:
            assert victims is None
    alloc.check()


def test_allocator_rejects_double_alloc_and_unknown_owner():
    alloc = PageAllocator(4)
    assert alloc.alloc("a", 2) is not None
    with pytest.raises(ValueError):
        alloc.alloc("a", 1)
    with pytest.raises(KeyError):
        alloc.extend("ghost", 1)
    with pytest.raises(KeyError):
        alloc.pin("ghost")
    assert alloc.free("ghost") == []    # free is idempotent by design


def test_allocator_truncate_keeps_pins_and_rejects_unknown():
    """Rollback must not disturb pin protection (the row being rolled
    back may be the one the scheduler is reclaiming *for*), and pinned
    owners' surviving pages stay out of the victim scan."""
    alloc = PageAllocator(8)
    alloc.alloc("a", 4)
    alloc.alloc("b", 4)
    alloc.pin("a")
    freed = alloc.truncate("a", 1)
    assert len(freed) == 3 and alloc.pinned("a")
    assert alloc.victims(4) == ["b"]      # pinned "a" never offered
    assert alloc.truncate("a", 99) == []  # keep >= held: no-op
    with pytest.raises(KeyError):
        alloc.truncate("ghost", 0)
    with pytest.raises(ValueError):
        alloc.truncate("a", -1)
    alloc.check()


def test_paged_kv_truncate_frees_suffix_and_trashes_table():
    """PagedKV.truncate keeps the page the next write lands in, frees
    the rest, and re-trashes their table entries so stale KV can never
    be read through this row again."""
    kv = PagedKV(num_layers=1, num_pages=8, page_size=4,
                 max_pages_per_row=4, max_batch=2, kv_heads=1, head_dim=8)
    assert kv.admit(0, 4)                       # covers 16 tokens
    pages = list(kv.allocator.pages_of(0))
    # roll back to 5 valid tokens: next write is position 5 -> page 1,
    # so pages 2..3 go home
    assert kv.truncate(0, 5) == 2
    assert kv.allocator.pages_of(0) == pages[:2]
    np.testing.assert_array_equal(kv.tables[0],
                                  pages[:2] + [kv.trash, kv.trash])
    assert kv.allocator.free_count == 8 - 2
    assert kv.truncate(0, 5) == 0               # idempotent
    # boundary: 8 valid tokens -> next write opens page 2, keep 3 pages
    kv.release(0)
    assert kv.admit(0, 4)
    assert kv.truncate(0, 8) == 1
    assert len(kv.allocator.pages_of(0)) == 3
    kv.allocator.check()


def test_allocator_free_unpins():
    alloc = PageAllocator(4)
    alloc.alloc("a", 4)
    alloc.pin("a")
    assert alloc.victims(1) is None
    alloc.free("a")
    alloc.alloc("b", 4)
    assert alloc.victims(2) == ["b"]    # "a"'s pin died with it


def test_paged_kv_admit_extend_release_tables():
    """Page-table rows mirror the allocator: admitted entries in order,
    everything else trash."""
    kv = PagedKV(num_layers=1, num_pages=6, page_size=4,
                 max_pages_per_row=3, max_batch=2, kv_heads=1, head_dim=8)
    assert kv.row_capacity() == 12
    assert kv.pages_for(1) == 1 and kv.pages_for(9) == 3
    assert kv.admit(0, 2)
    pages = kv.allocator.pages_of(0)
    np.testing.assert_array_equal(kv.tables[0],
                                  pages + [kv.trash] * (3 - len(pages)))
    assert kv.extend(0, 1)
    assert kv.tables[0, 2] == kv.allocator.pages_of(0)[2]
    assert not kv.extend(0, 99)
    kv.release(0)
    assert (kv.tables[0] == kv.trash).all()
    assert kv.allocator.free_count == 6


# ---------------------------------------------------------------------------
# paged_attention kernel vs gather oracle (interpret mode off-TPU)
# ---------------------------------------------------------------------------

def _paged_inputs(bsz, h, hkv, dh, num_pages, ps, p, seed, ragged=True):
    ks = jax.random.split(jax.random.fold_in(KEY, seed), 4)
    q = jax.random.normal(ks[0], (bsz, h, dh))
    kp = jax.random.normal(ks[1], (num_pages + 1, ps, hkv, dh))
    vp = jax.random.normal(ks[2], (num_pages + 1, ps, hkv, dh))
    rng = np.random.RandomState(seed)
    perm = rng.permutation(num_pages)[:bsz * p].reshape(bsz, p)
    tables = jnp.asarray(perm, jnp.int32)
    if ragged:
        lens = jnp.asarray(rng.randint(0, p * ps + 1, bsz), jnp.int32)
    else:
        lens = jnp.full((bsz,), p * ps, jnp.int32)
    return q, kp, vp, tables, lens


@settings(max_examples=6, deadline=None)
@given(dh=st.sampled_from([16, 32, 100, 128]),
       hkv=st.sampled_from([1, 2]),
       groups=st.sampled_from([1, 2, 4]),
       seed=st.integers(min_value=0, max_value=10_000))
def test_paged_attn_unaligned_head_dims(dh, hkv, groups, seed):
    """Head dims off the 128-lane grid: the wrapper pads and slices back
    (with the softmax scale taken from the true Dh)."""
    q, kp, vp, tables, lens = _paged_inputs(
        3, hkv * groups, hkv, dh, 12, 8, 4, seed)
    got = ops.paged_attention(q, kp, vp, tables, lens, page_size=8,
                              interpret=True)
    want = ref.paged_attention_ref(q, kp, vp, tables, lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@settings(max_examples=6, deadline=None)
@given(ps=st.sampled_from([4, 8, 16]),
       p=st.sampled_from([1, 3, 5]),
       seed=st.integers(min_value=0, max_value=10_000))
def test_paged_attn_ragged_rows_and_multi_page(ps, p, seed):
    """Ragged per-row lengths (including 0 and exactly-full), rows
    spanning several pages, sublane-padded page sizes."""
    q, kp, vp, tables, lens = _paged_inputs(4, 4, 2, 32, p * 4 + 2, ps, p,
                                            seed)
    got = ops.paged_attention(q, kp, vp, tables, lens, page_size=ps,
                              interpret=True)
    want = ref.paged_attention_ref(q, kp, vp, tables, lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_paged_attn_matches_contiguous_attention():
    """Scattering a contiguous KV sequence into shuffled pages and
    reading it back through the page table must reproduce dense masked
    attention over the contiguous layout."""
    from repro.models.common import attention
    bsz, h, hkv, dh, ps, p = 2, 4, 2, 32, 4, 4
    ks = jax.random.split(KEY, 3)
    skv = p * ps
    q = jax.random.normal(ks[0], (bsz, 1, h, dh))
    k = jax.random.normal(ks[1], (bsz, skv, hkv, dh))
    v = jax.random.normal(ks[2], (bsz, skv, hkv, dh))
    lens = jnp.asarray([skv, 7], jnp.int32)
    # scatter rows into a shuffled page pool
    rng = np.random.RandomState(0)
    perm = rng.permutation(bsz * p).reshape(bsz, p)
    kp = jnp.zeros((bsz * p + 1, ps, hkv, dh))
    vp = jnp.zeros((bsz * p + 1, ps, hkv, dh))
    for b in range(bsz):
        for j in range(p):
            kp = kp.at[perm[b, j]].set(k[b, j * ps:(j + 1) * ps])
            vp = vp.at[perm[b, j]].set(v[b, j * ps:(j + 1) * ps])
    tables = jnp.asarray(perm, jnp.int32)
    got = ops.paged_attention(q[:, 0], kp, vp, tables, lens, page_size=ps,
                              interpret=True)
    kv_pos = jnp.broadcast_to(jnp.arange(skv)[None, :], (bsz, skv))
    want = attention(q, k, v, causal=False,
                     kv_positions=kv_pos,
                     kv_valid=kv_pos < lens[:, None])[:, 0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# plain decode attention over the pool in place vs the gather oracle
# ---------------------------------------------------------------------------

# Rows of 4-slot pages from a 12-page pool (page 12 is the trash page):
# (pages the row holds in table order, valid tokens). Unlisted table
# entries are trash; every slot of every page, the trash page included,
# holds random KV, so a slot the mask lets through shows.
POOL_ROWS = {
    # mid-page; one token past a boundary; a lone first token
    "boundary_and_mid_page": [([3, 7], 5), ([0, 5, 9], 9), ([2], 1),
                              ([11, 4, 8, 6], 14)],
    "exact_page_multiple": [([3, 7], 8), ([0, 5, 9, 1], 16), ([2], 4),
                            ([10], 4)],
    # inactive rows hold nothing: all-trash tables, length 0
    "inactive_rows": [([], 0), ([0, 5], 7), ([], 0), ([], 0)],
    # page 5 went from row 0 back to the pool and on to row 1, which has
    # written only its first slot: slots 1-3 keep row 0's stale KV
    "reused_stale_page": [([3], 2), ([6, 5], 5), ([1, 2, 4], 12),
                          ([], 0)],
}


@pytest.mark.parametrize("h,hkv", [(4, 4), (8, 2)], ids=["mha", "gqa"])
@pytest.mark.parametrize("case", sorted(POOL_ROWS))
def test_pool_decode_attention_matches_gather_oracle(case, h, hkv):
    """The plain decode path reads the whole layer pool under a per-row
    validity mask built from the tables; it must equal the gather
    oracle, f32 on the CPU, row for row (empty rows emit zeros)."""
    from repro.serve.engine import pool_decode_attention, pool_kv_valid
    num_pages, ps, p, dh = 12, 4, 4, 16
    rows = POOL_ROWS[case]
    tables = np.full((len(rows), p), num_pages, np.int32)
    lens = np.zeros((len(rows),), np.int32)
    for b, (pages, n) in enumerate(rows):
        tables[b, :len(pages)] = pages
        lens[b] = n
    ks = jax.random.split(jax.random.fold_in(KEY, 7), 3)
    q = jax.random.normal(ks[0], (len(rows), h, dh))
    kp = jax.random.normal(ks[1], (num_pages + 1, ps, hkv, dh))
    vp = jax.random.normal(ks[2], (num_pages + 1, ps, hkv, dh))
    tables, lens = jnp.asarray(tables), jnp.asarray(lens)
    valid = pool_kv_valid(tables, lens, num_pages + 1, ps)
    got = pool_decode_attention(q, kp, vp, valid)
    want = ref.paged_attention_ref(q, kp, vp, tables, lens)
    assert got.dtype == want.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    # each row attends exactly its own written positions
    assert np.asarray(valid).sum(axis=1).tolist() == [n for _, n in rows]
