"""The traffic generators are deterministic given the seed, and every
seed gets the same sizes in another order."""
import numpy as np

import bench
import fixtures
from drivers import fed, serve

BIG = 2 ** 31 + 977


def test_fed_traffic_is_a_function_of_the_seed():
    tr = fixtures.read("perfbench/traffic/sync_mrpc.json")
    a = fed.partition(tr, BIG), fed.client_ranks(tr, BIG), \
        fed.client_batches(BIG, 3, 17, tr, 50265, 0.4)
    b = fed.partition(tr, BIG), fed.client_ranks(tr, BIG), \
        fed.client_batches(BIG, 3, 17, tr, 50265, 0.4)
    for x, y in zip(a, b):
        for u, v in zip(x, y):
            np.testing.assert_array_equal(u, v)
    c = fed.client_batches(BIG + 1, 3, 17, tr, 50265, 0.4)
    assert not np.array_equal(a[2][0], c[0])


def test_fed_rows_differ_and_fit():
    tr = fixtures.read("perfbench/traffic/sync_mrpc.json")
    toks, labels = fed.client_batches(5, 0, 1, tr, 50265, 0.5)
    flat = toks.reshape(-1, toks.shape[-1])
    assert len({r.tobytes() for r in flat}) == len(flat)
    assert toks.shape == (8, 4, 128) and labels.shape == (8, 4)
    assert flat.max() < 50265 and (flat[:, 0] == fed.CLS).all()
    content = (flat != fed.PAD).sum(1)
    assert content.min() >= 40 and content.max() <= 110


def test_fed_partition_sizes():
    for name in ("sync_mrpc", "xdevice"):
        tr = fixtures.read(f"perfbench/traffic/{name}.json")
        sizes, p = fed.partition(tr, 11)
        assert len(sizes) == tr["clients"]
        assert sizes.min() >= tr["min_examples"]
        assert ((p > 0) & (p < 1)).all()


def test_serve_requests_same_multiset_other_order():
    for name in ("chat", "rag"):
        tr = fixtures.serve_mix(name)
        a = serve.requests(tr, 1, 30.0, 16)
        b = serve.requests(tr, 1, 30.0, 16)
        c = serve.requests(tr, BIG, 30.0, 16)
        for x, y, z in zip(a, b, c):
            if x is None:
                continue
            np.testing.assert_array_equal(x, y)
            np.testing.assert_allclose(np.sort(np.diff(np.r_[0, x]))
                                       if x is a[0] else np.sort(x),
                                       np.sort(np.diff(np.r_[0, z]))
                                       if x is a[0] else np.sort(z))
        assert not np.array_equal(a[1], c[1])
        p, o = a[1], a[2]
        assert p.max() + o.max() <= 2047
        assert p.min() >= tr["prompt"]["min"] and \
            p.max() <= tr["prompt"]["max"]


def test_chat_arrivals_fill_the_window_at_the_rate():
    tr = fixtures.serve_mix("chat")
    arr = serve.requests(tr, 3, 40.0, 16)[0]
    assert len(arr) == round(tr["rate"] * 40)
    assert abs(arr[-1] - 40.0) < 0.25 * 40.0


def test_prompt_tokens_are_seeded():
    a = serve.prompt_tokens(BIG, 4, 100, 32064)
    b = serve.prompt_tokens(BIG, 4, 100, 32064)
    np.testing.assert_array_equal(a, b)
    assert a.min() >= 3 and a.max() < 32064


def test_seeds_wider_than_32_bits():
    a = bench.np_rng(2 ** 33 + 5, "x").integers(0, 1 << 30, 4)
    b = bench.np_rng(5, "x").integers(0, 1 << 30, 4)
    assert not np.array_equal(a, b)
