"""Serving-layer contract tests: coalescing exactness, entrypoint caching,
transfer pooling, and session observability.

The load-bearing property is **coalescing exactness**: any interleaving and
grouping of probe requests through ``JoinSession`` — merged fast-path
batches, sequential fallbacks, forced-capacity overflows, empty and
oversized requests — must yield, per request, a pair list and a
``JoinStats`` bit-identical to probing that request alone through
``JoinEngine.probe``.  The sweeps below sample request mixes and flush
cadences and compare every ticket against a fresh sequential oracle.
"""

import dataclasses
import threading

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:  # container has no pip index — seeded fallback
    from _propstrat import given, settings, strategies as st

from repro.core import verify
from repro.core.collection import from_lists
from repro.core.engine import JoinEngine, prepare
from repro.serve import (
    EntrypointCache,
    JoinSession,
    RequestCoalescer,
    TransferPool,
    pow2_bucket,
)

SIM, TAU = "jaccard", 0.7
_PAD = 12  # fixed padded width -> stable jit/bucket shapes across examples


def _corpus(seed: int = 3, n: int = 250):
    """Dup-heavy corpus: near-copies force real pairs and, under a forced
    tiny capacity, solo-probe overflows."""
    rng = np.random.default_rng(seed)
    base = [rng.choice(140, size=rng.integers(3, 11), replace=False).tolist()
            for _ in range(30)]
    sets = []
    for _ in range(n):
        src = base[int(rng.integers(len(base)))]
        kept = [t for t in src if rng.random() > 0.2]
        sets.append(kept or src[:1])
    return from_lists(sets, pad_to=_PAD)


def _requests(seed: int, corpus_sets):
    """A mixed request stream: singletons, small batches, empties, and
    exact corpus rows (guaranteed matches)."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(int(rng.integers(3, 9))):
        rows = int(rng.integers(0, 5))
        sets = []
        for _ in range(rows):
            if rng.random() < 0.5:
                sets.append(list(corpus_sets[int(rng.integers(
                    len(corpus_sets)))]))
            else:
                sz = int(rng.integers(1, 11))
                sets.append(rng.choice(140, size=sz,
                                       replace=False).tolist())
        out.append(from_lists(sets, pad_to=_PAD))
    return out


@pytest.fixture(scope="module")
def corpus():
    return _corpus()


@pytest.fixture(scope="module")
def session(corpus):
    return JoinSession(corpus, SIM, TAU, max_batch=16, max_wait=0.0)


@pytest.fixture(scope="module")
def oracle(corpus, session):
    return JoinEngine(prepare(corpus), SIM, TAU, plan=session.plan)


@pytest.fixture(scope="module")
def forced_session(corpus, session):
    # A forced tiny capacity: requests whose solo probe would overflow the
    # chunk (dense-fallback escalation) must route sequentially.
    plan = dataclasses.replace(session.plan, capacity=48)
    return JoinSession(corpus, SIM, TAU, plan=plan, max_batch=16,
                       max_wait=0.0)


@pytest.fixture(scope="module")
def forced_oracle(corpus, forced_session):
    return JoinEngine(prepare(corpus), SIM, TAU, plan=forced_session.plan)


def _assert_tickets_match_oracle(tickets, requests, oracle):
    for t, r in zip(tickets, requests):
        want_pairs, want_stats = oracle.probe(r)
        got_pairs, got_stats = t.result()
        assert np.array_equal(got_pairs, want_pairs), (
            f"pairs diverge (route={t.route}, rows={r.num_sets})")
        assert got_stats == want_stats, (
            f"stats diverge (route={t.route}, rows={r.num_sets}): "
            f"{got_stats} != {want_stats}")


@settings(max_examples=12, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10**6))
def test_coalescing_exactness_sweep(session, oracle, corpus, seed):
    """Any request mix/interleaving: per-request results == solo probes."""
    rng = np.random.default_rng(seed + 1)
    requests = _requests(seed, corpus.as_lists())
    tickets = []
    for r in requests:
        tickets.append(session.submit(r))
        if rng.random() < 0.35:  # sampled flush cadence -> varied groupings
            session.flush()
    session.flush()
    _assert_tickets_match_oracle(tickets, requests, oracle)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10**6))
def test_coalescing_exactness_forced_overflow(forced_session, forced_oracle,
                                              corpus, seed):
    """Forced-capacity chunks (solo dense-fallback escalation) stay
    bit-identical — the session must route them through the engine."""
    requests = _requests(seed, corpus.as_lists())
    tickets = [forced_session.submit(r) for r in requests]
    forced_session.flush()
    _assert_tickets_match_oracle(tickets, requests, forced_oracle)


def test_forced_overflow_actually_routes_sequentially(forced_session, corpus):
    """The overflow guard must fire on this corpus (otherwise the sweep
    above never exercises the dense-fallback path)."""
    sets = corpus.as_lists()
    req = from_lists([sets[i] for i in range(8)], pad_to=_PAD)
    n_exp, _lp = forced_session._prepass(req)
    assert n_exp > 48  # the forced capacity
    t = forced_session.submit(req)
    forced_session.flush()
    assert t.route == "sequential"
    assert t.stats.overflow_blocks >= 1  # solo run escalated, and we match


def test_steady_state_zero_retraces(corpus):
    sess = JoinSession(corpus, SIM, TAU, max_batch=16, max_wait=0.0)
    stream = _requests(11, corpus.as_lists())
    for r in stream:
        sess.submit(r)
    sess.flush()
    warm = sess.entrypoints.stats()["traces"]
    for _ in range(3):  # identical replay -> identical buckets
        for r in stream:
            sess.submit(r)
        sess.flush()
    ep = sess.entrypoints.stats()
    assert ep["traces"] == warm, "entrypoints retraced at steady state"
    assert ep["max_traces_per_key"] == 1
    assert ep["hits"] >= 3


def test_warm_buckets_precompiles_ladder(corpus):
    sess = JoinSession(corpus, SIM, TAU, max_batch=16, max_wait=0.0)
    sample = [from_lists([s], pad_to=_PAD) for s in corpus.as_lists()[:8]]
    compiled = sess.warm_buckets(sample)
    assert compiled >= 1
    warm = sess.entrypoints.stats()["traces"]
    for r in sample * 4:  # any grouping of the sampled shapes
        sess.submit(r)
    sess.flush()
    assert sess.entrypoints.stats()["traces"] == warm


def test_warm_buckets_covers_narrower_traffic(corpus):
    """Requests the sample did not include, with shorter sets and prefixes
    than any sampled group, run on the warm entrypoints — exactly."""
    by_len = sorted(corpus.as_lists(), key=len)
    sess = JoinSession(corpus, SIM, TAU, max_batch=16, max_wait=0.0)
    assert sess.warm_buckets([from_lists([s]) for s in by_len[-16:]]) >= 1
    warm = sess.entrypoints.stats()["traces"]
    traffic = [from_lists([s]) for s in by_len[:40]]
    tickets = [sess.submit(r) for r in traffic]
    sess.flush()
    assert sess.entrypoints.stats()["traces"] == warm
    oracle = JoinEngine(sess.prepared, SIM, TAU, plan=sess.plan)
    for t, req in zip(tickets, traffic):
        pairs, stats = t.result()
        want_pairs, want_stats = oracle.probe(req, return_stats=True)
        assert t.route == "coalesced"
        np.testing.assert_array_equal(pairs, want_pairs)
        assert stats == want_stats


def test_session_probe_matches_engine_semantics(session, oracle, corpus):
    req = from_lists(corpus.as_lists()[:3], pad_to=_PAD)
    pairs, stats = session.probe(req)
    want_pairs, want_stats = oracle.probe(req)
    assert np.array_equal(pairs, want_pairs)
    assert stats == want_stats
    assert session.probe(req, return_stats=False).shape == pairs.shape


def test_session_stats_summary(session):
    s = session.stats_summary()
    for key in ("engine", "entrypoints", "transfer", "min_overlap_cache",
                "requests", "coalesced_requests", "sequential_requests",
                "coalesced_batches", "pad_overhead", "builds"):
        assert key in s, key
    assert s["builds"]["sort"] == 1 and s["builds"]["bitmap"] == 1
    assert s["requests"] == (s["coalesced_requests"]
                             + s["sequential_requests"])
    assert s["engine"]["probes"] == s["requests"]
    assert s["pad_overhead"] >= 0.0


# ---------------------------------------------------------------------------
# Component units: coalescer, entrypoint cache, transfer pool, verify cache
# ---------------------------------------------------------------------------


def _req(rows: int):
    return from_lists([[1 + i, 2 + i, 3 + i] for i in range(rows)],
                      pad_to=4)


def test_coalescer_due_policy():
    c = RequestCoalescer(max_batch=4, max_wait=1.0)
    assert not c.due(now=0.0)
    c.submit(_req(1), now=0.0)
    assert not c.due(now=0.5)      # neither full nor aged
    assert c.due(now=1.0)          # oldest hit max_wait
    c.submit(_req(3), now=0.1)
    assert c.due(now=0.2)          # full batch pending
    assert c.pending_rows == 4


def test_coalescer_drain_grouping():
    c = RequestCoalescer(max_batch=4, max_wait=0.0)
    rows = [2, 1, 2, 4, 6, 1]
    tickets = [c.submit(_req(r)) for r in rows]
    groups = c.drain()
    # FIFO first-fit: [2,1] | [2] (4 won't fit) | [4] | [6 oversized] | [1]
    got = [[t.rows for t in g] for g in groups]
    assert got == [[2, 1], [2], [4], [6], [1]]
    assert [t.seq for g in groups for t in g] == [t.seq for t in tickets]
    assert len(c) == 0 and c.drained_groups == 5


def test_coalescer_validation():
    with pytest.raises(ValueError):
        RequestCoalescer(max_batch=0)
    with pytest.raises(ValueError):
        RequestCoalescer(max_wait=-1.0)
    t = RequestCoalescer().submit(_req(1))
    with pytest.raises(RuntimeError):
        t.result()


def test_pow2_bucket():
    assert [pow2_bucket(n) for n in (0, 1, 2, 3, 5, 16, 17)] == \
        [1, 1, 2, 4, 8, 16, 32]
    assert pow2_bucket(3, floor=16) == 16
    assert pow2_bucket(100, floor=16) == 128


def test_entrypoint_cache_builds_once_and_counts():
    cache = EntrypointCache(maxsize=2)
    built = []

    def mk(key):
        def build():
            built.append(key)
            def fn():
                cache.note_trace(key)
                return key
            return fn
        return build

    a = cache.get("a", mk("a"))
    assert cache.get("a", mk("a")) is a
    assert built == ["a"]
    a(), a()
    s = cache.stats()
    assert s["traces"] == 2 and s["max_traces_per_key"] == 2
    cache.get("b", mk("b"))
    cache.get("c", mk("c"))   # evicts "a" (LRU, maxsize=2)
    s = cache.stats()
    assert s["entries"] == 2 and s["misses"] == 3 and s["hits"] == 1
    assert s["max_traces_per_key"] == 0  # eviction drops "a"'s trace count
    assert built == ["a", "b", "c"]
    cache.get("a", mk("a"))   # rebuilt after eviction
    assert built == ["a", "b", "c", "a"]


def test_transfer_pool_reuses_buffers():
    pool = TransferPool(depth=2)
    arrays = [np.arange(6, dtype=np.int32).reshape(2, 3),
              np.ones(2, dtype=np.int32)]
    for i in range(5):
        dev = pool.upload("k", [a + i for a in arrays])
        assert np.array_equal(np.asarray(dev[0]), arrays[0] + i)
    s = pool.stats()
    assert s["uploads"] == 5
    assert s["slot_builds"] == 2  # ring filled once, then reused
    assert s["buckets"] == 1
    # A signature change (the bucket widened) rebuilds the ring.
    pool.upload("k", [np.zeros((4, 3), np.int32), np.ones(4, np.int32)])
    assert pool.stats()["slot_builds"] == 3
    with pytest.raises(ValueError):
        TransferPool(depth=0)


def test_min_overlap_cache_locked_and_counted():
    verify._TABLE_CACHE.clear()
    base = verify.min_overlap_cache_stats()
    assert base["entries"] == 0

    errs = []

    def hammer():
        try:
            for i in range(20):
                verify.min_overlap_table_dev(SIM, TAU, 16 + (i % 3), 16)
        except Exception as e:  # pragma: no cover - failure capture
            errs.append(e)

    threads = [threading.Thread(target=hammer) for _ in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    s = verify.min_overlap_cache_stats()
    assert s["entries"] == 3
    assert s["hits"] + s["misses"] == 6 * 20
    assert s["misses"] >= 3
    # Same key twice -> identical device table object (cache hit).
    t1 = verify.min_overlap_table_dev(SIM, TAU, 16, 16)
    t2 = verify.min_overlap_table_dev(SIM, TAU, 16, 16)
    assert t1 is t2
