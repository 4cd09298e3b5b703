"""The plain reference against the program's own oracle and the chip
smoke's host reference, the decimal threshold, and the control."""

from fractions import Fraction

import numpy as np
import pytest

from chipbench import compare, generate as g, reference
from chipbench.tests.conftest import tiny
from repro.core.collection import Collection
from repro.core.join import naive_join


@pytest.fixture(scope="module")
def corpus():
    return g.shuffle(g.make_corpus(tiny("dblp-dedup", n_sets=1000,
                                        clusters=10)), 2 ** 31 + 3)


def test_need_table_is_the_decimal_threshold():
    need = reference.need_table(0.9, 600)
    for k in range(601):
        # least o with o / (k - o) >= 9 / 10
        o = next(o for o in range(k + 1) if k == 0 or 10 * o >= 9 * (k - o))
        assert need[k] == o
    # |r| = |s| = 152, o = 144: Jaccard exactly 0.9 is a pair.
    assert need[304] == 144
    assert Fraction(144, 304 - 144) == Fraction(9, 10)
    assert np.array_equal(reference.need_table(0.5, 90),
                          [-(-k // 3) for k in range(91)])


@pytest.mark.parametrize("tau", [0.9, 0.5])
def test_self_join_equals_naive_join(corpus, tau):
    want = naive_join(Collection(corpus.tokens, corpus.lengths), "jaccard",
                      tau)
    got = reference.DeviceReference(corpus.tokens, corpus.lengths, tau,
                                    block=256).pairs()
    assert len(got) > 10
    assert np.array_equal(got, want)


@pytest.mark.parametrize("tau", [0.9, 0.5])
def test_queries_equal_the_host_reference(corpus, tau):
    """Queries: 150 rows of the corpus and 150 rows of another corpus."""
    other = g.make_corpus(dict(tiny("dblp-dedup", n_sets=300, clusters=0),
                               corpus_seed=4))
    q = np.full((300, max(corpus.tokens.shape[1], other.tokens.shape[1])),
                g.PAD, np.int32)
    q[:150, :corpus.tokens.shape[1]] = corpus.tokens[:150]
    q[150:, :other.tokens.shape[1]] = other.tokens[:150]
    ql = np.concatenate([corpus.lengths[:150], other.lengths[:150]])
    got = reference.DeviceReference(corpus.tokens, corpus.lengths, tau,
                                    block=128).pairs(q, ql)
    host = reference.HostReference(corpus.tokens, corpus.lengths)
    assert len(got) >= 150
    for k in range(300):
        assert np.array_equal(got[got[:, 1] == k, 0],
                              host.partners(q[k, :ql[k]], tau))


def test_control_reports_a_superset_and_fails(corpus):
    """The control (verification skipped, bitmap bound only) keeps every
    exact pair and adds pairs the bound cannot rule out: it fails the
    comparison a run makes."""
    ref = reference.DeviceReference(corpus.tokens, corpus.lengths, 0.9,
                                    block=256)
    want = ref.pairs()
    ctl = ref.control_pairs(128)
    missing, extra = compare.missing_extra(ctl, want, corpus.num_sets)
    assert missing == 0
    assert extra > 0


def test_missing_extra_counts_duplicates_as_extra():
    want = np.array([[0, 1], [2, 3]])
    assert compare.missing_extra(want, want, 10) == (0, 0)
    assert compare.missing_extra(want[:1], want, 10) == (1, 0)
    assert compare.missing_extra(np.array([[0, 1], [0, 1], [2, 3]]), want,
                                 10) == (0, 1)
    assert compare.missing_extra(np.array([[0, 1], [2, 4]]), want,
                                 10) == (1, 1)
