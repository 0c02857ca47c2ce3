"""The ``instruct`` traffic mix: determinism in the seed (wide seeds
included), the lengths and the category mix it was given."""
import numpy as np

import fixtures
from drivers import fed_lm

TR = fixtures.read("perfbench/traffic/instruct.json")
V = 12544
BIG = 2 ** 33 + 17


def _client(seed, rnd, cid):
    sizes, mix = fed_lm.partition(TR, seed)
    cdfs = fed_lm.unigrams(TR, seed, V)
    return fed_lm.client_batches(seed, rnd, cid, TR, mix[cid], cdfs)


def test_same_seed_same_data_other_seed_other_data():
    a, b = _client(BIG, 3, 7), _client(BIG, 3, 7)
    for x, y in zip(a[:2], b[:2]):
        np.testing.assert_array_equal(x, y)
    c = _client(BIG + 1, 3, 7)
    assert not np.array_equal(a[0], c[0])
    assert not np.array_equal(a[0], _client(BIG, 4, 7)[0])


def test_partition_mix():
    sizes, mix = fed_lm.partition(TR, BIG)
    assert sizes.shape == (100,) and sizes.min() >= TR["min_examples"]
    assert abs(sizes.sum() - TR["examples"]) < 100 * TR["min_examples"]
    np.testing.assert_allclose(mix.sum(1), 1.0)
    # Dirichlet(0.5) over 8 categories: most clients lean on one or two
    assert np.median(mix.max(1)) > 0.35


def test_lengths_labels_and_ids():
    rng = np.random.default_rng(0)
    cdfs = fed_lm.unigrams(TR, 5, V)
    rows = [fed_lm.example(rng, TR, cdfs[i % 8]) for i in range(600)]
    s = TR["seq_len"]
    for toks, labels, n, pred in rows:
        assert toks.shape == labels.shape == (s,) and n <= s
        assert (toks[n:] == fed_lm.PAD).all() and toks[n - 1] == fed_lm.EOS
        assert int((labels >= 0).sum()) == pred
        assert toks[:n].min() >= 0 and toks.max() < V
        # a label is the next token
        i = np.nonzero(labels >= 0)[0]
        np.testing.assert_array_equal(labels[i], toks[i + 1])
    prompt = np.array([n - 2 - p for _, _, n, p in rows])
    resp = np.array([p - 1 for _, _, n, p in rows])
    assert 140 < np.median(prompt) < 180
    assert 195 < np.median(resp) < 255
    n = np.array([r[2] for r in rows])
    assert n.max() <= s and 0.3 < n.mean() / s < 0.7
