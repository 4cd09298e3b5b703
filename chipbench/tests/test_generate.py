"""The benchmark's generators: the corpus holds the published statistics
of its collection, each set is a weighted sample without replacement, the
preprocessing is the program's, planted pairs reach their Jaccard, and a
seed fixes every input."""

import numpy as np
import pytest

from chipbench import generate as g
from chipbench.tests.conftest import tiny
from repro.core.collection import from_lists, preprocess

PAD = g.PAD


def _rows(tokens, lengths):
    return [tokens[i, :lengths[i]].tolist() for i in range(len(lengths))]


def test_zipf_cdf_matches_its_law():
    cdf = g.zipf_cdf(1.15, 6864)
    pmf = np.diff(np.concatenate([[0.0], cdf]))
    k = np.arange(1, 6865, dtype=np.float64)
    assert np.allclose(pmf, k ** -1.15 / (k ** -1.15).sum())
    assert cdf[-1] == 1.0 and np.all(np.diff(cdf) > 0)


def test_inverse_cdf_table_is_exact():
    cdf = g.zipf_cdf(1.15, 6864)
    u = np.random.default_rng(1).random(200_000)
    assert np.array_equal(g._InverseCdf(cdf, bits=12)(u),
                          np.searchsorted(cdf, u, side="right"))


def test_sets_are_weighted_samples_without_replacement():
    """Every row holds exactly its size of distinct tokens of the universe;
    a token's share of the rows follows the first-draw probability of
    successive sampling (the most likely token is in nearly every set)."""
    cfg = tiny("dblp-dedup")
    sizes = np.random.default_rng(3).integers(1, 300, size=2000)
    sizes[:3] = cfg["sizes"]["max"]
    raw = g.draw_sets(cfg, sizes, np.random.default_rng(7))
    lens = (raw != PAD).sum(axis=1)
    assert np.array_equal(lens, sizes)
    for row, n in zip(raw, lens):
        assert len(np.unique(row[:n])) == n
    assert raw[raw != PAD].max() < cfg["tokens"]["n_tokens"]
    share = np.bincount(raw[raw != PAD]) / len(raw)
    assert share[0] > 0.9 and share[0] > share[100] > share[3000]
    again = g.draw_sets(cfg, sizes, np.random.default_rng(7))
    assert np.array_equal(raw, again)


def test_sets_follow_the_program_generator():
    """The token law is the program generator's (Zipf 1.15); the sizes and
    the universe are the published DBLP numbers: mean 82.7, largest 869,
    6,864 distinct tokens."""
    cfg = tiny("dblp-dedup", n_sets=20000, clusters=0)
    assert cfg["tokens"] == {"n_tokens": 6864, "zipf_a": 1.15}
    sizes = g.set_sizes(cfg, 100_000, np.random.default_rng(11))
    assert abs(sizes.mean() - 82.7) < 0.01 * 82.7
    assert sizes.max() == 869 and sizes.min() >= 8
    mine = g.make_corpus(cfg)
    assert abs(mine.lengths.mean() - 82.7) < 0.02 * 82.7
    assert mine.lengths.max() == 869
    assert mine.tokens.shape[1] == 869
    assert mine.vocab <= 6864 and mine.vocab > 0.9 * 6864


def test_preprocess_equals_the_programs():
    cfg = tiny("dblp-dedup", n_sets=800, clusters=8)
    rng = np.random.default_rng(5)
    raw = g.plant_clusters(cfg, g.draw_sets(
        cfg, g.set_sizes(cfg, 784, rng), rng), rng)
    mine = g.preprocess(raw)
    theirs = preprocess(from_lists(_rows(raw, (raw != PAD).sum(axis=1))))
    assert np.array_equal(mine.tokens, theirs.tokens)
    assert np.array_equal(mine.lengths, theirs.lengths)


def test_planted_copies_reach_their_jaccard():
    """Each copy sits at Jaccard round(2 j n / (1 + j)) / (2 n - keep) to
    its source and draws its new tokens from the universe."""
    cfg = tiny("dblp-dedup", n_sets=900, clusters=50)
    rng = np.random.default_rng(2)
    base = g.draw_sets(cfg, g.set_sizes(cfg, 800, rng), rng)
    out = g.plant_clusters(cfg, base, np.random.default_rng(4))
    lens = (out != PAD).sum(axis=1)
    assert out[out != PAD].max() < cfg["tokens"]["n_tokens"]
    base_sets = [set(r[:n]) for r, n in zip(base, lens[:800])]
    for k in range(800, 900):
        copy = set(out[k, :lens[k]])
        assert len(copy) == lens[k]
        best = max(len(copy & s) / len(copy | s) for s in base_sets
                   if len(s) == len(copy))
        n = len(copy)
        keep = min(max(round(2 * 0.95 * n / 1.95), 1), n)
        assert best == pytest.approx(keep / (2 * n - keep))
        assert best >= 0.9


def test_shuffle_keeps_the_corpus_and_moves_its_rows():
    corpus = g.make_corpus(tiny("dblp-dedup", n_sets=500, clusters=5))
    a, b = g.shuffle(corpus, 1), g.shuffle(corpus, 2)
    key = lambda c: sorted(map(tuple, c.tokens))
    assert key(a) == key(corpus) == key(b)
    assert not np.array_equal(a.tokens, b.tokens)
