"""Oracle-differential property harness for the compaction/escalation paths.

Exactness is the product: every join driver — host compaction, the
device-resident compaction path (prepass-sized and forced-tiny capacities
that overflow into the dense escalation) — must return *exactly* the
``naive_join`` oracle's pair set for every similarity function, threshold
and collection shape.  The harness samples sim ∈ {jaccard, cosine, dice,
overlap}, τ across [0.5, 0.95] (absolute thresholds for overlap), and
uniform / skewed / duplicate-heavy collections, and additionally asserts the
``JoinStats`` invariants and the host-vs-device bit-for-bit counter match.
"""

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:  # container has no pip index — seeded fallback
    from _propstrat import given, settings, strategies as st

from repro.core import join
from repro.core.collection import from_lists

# sim × τ grid spanning the acceptance range; overlap takes absolute counts.
SIM_TAUS = ([(s, t) for s in ("jaccard", "cosine", "dice")
             for t in (0.5, 0.7, 0.85, 0.95)]
            + [("overlap", 2.0), ("overlap", 5.0)])

_PAD = 16  # fixed padded width -> one jit cache across examples
KINDS = ("uniform", "skewed", "dup_heavy")


def _collection(kind: str, seed: int, n: int = 48):
    rng = np.random.default_rng(seed)
    if kind == "uniform":
        sets = [rng.choice(110, size=rng.integers(1, 13), replace=False).tolist()
                for _ in range(n)]
    elif kind == "skewed":
        # Zipf-distributed token draws: a few tokens appear in most sets.
        sets = []
        for _ in range(n):
            sz = int(rng.integers(1, 13))
            toks = np.unique(np.minimum(rng.zipf(1.3, size=3 * sz + 4), 140))[:sz]
            sets.append(toks.tolist())
    elif kind == "dup_heavy":
        # Near-copies of a small base pool: dense candidate tiles, many true
        # pairs — the capacity-overflow stressor.
        base = [rng.choice(110, size=rng.integers(2, 13), replace=False).tolist()
                for _ in range(max(n // 4, 1))]
        sets = []
        for _ in range(n):
            src = base[int(rng.integers(len(base)))]
            kept = [t for t in src if rng.random() > 0.15]
            sets.append(kept or src[:1])
    else:
        raise KeyError(kind)
    return from_lists(sets, pad_to=_PAD)


def _check_invariants(stats: join.JoinStats):
    assert 0.0 <= stats.filter_ratio <= 1.0, stats
    assert 0.0 <= stats.precision <= 1.0, stats
    assert stats.verified_true <= stats.candidates <= stats.total_pairs, stats
    assert stats.blocks_skipped <= stats.blocks_total, stats
    assert stats.overflow_blocks >= 0, stats


@settings(max_examples=12, deadline=None)
@given(seed=st.integers(0, 10_000), simtau=st.sampled_from(SIM_TAUS),
       kind=st.sampled_from(KINDS))
def test_device_resident_join_matches_oracle(seed, simtau, kind):
    """Self-join: host path, device-resident path and the oracle all agree;
    JoinStats counters match bit-for-bit between the two compaction modes."""
    sim, tau = simtau
    col = _collection(kind, seed)
    oracle = join.naive_join(col, sim, tau)
    host, hstats = join.blocked_bitmap_join(
        col, sim, tau, b=32, block=16, return_stats=True)
    dev, dstats = join.blocked_bitmap_join(
        col, sim, tau, b=32, block=16, compaction="device", return_stats=True)
    assert np.array_equal(oracle, host), (sim, tau, kind, len(oracle), len(host))
    assert np.array_equal(oracle, dev), (sim, tau, kind, len(oracle), len(dev))
    assert hstats == dstats, (hstats, dstats)
    assert dstats.overflow_blocks == 0  # prepass-sized capacity never overflows
    _check_invariants(dstats)


@pytest.mark.parametrize("kind", ["dup_heavy", "skewed"])
def test_lookup_compaction_matches_oracle(kind):
    """Blocks of 512 give sparse tiles, so the resident step compacts them
    by lookup (``kernels.compaction.compact_indices``; the skewed corpus
    also has tiles dense enough for the scatter form): pairs equal the
    oracle's and the host path's, counters the host path's."""
    col = _collection(kind, seed=11, n=2048)
    oracle = join.naive_join(col, "jaccard", 0.8)
    host, hstats = join.blocked_bitmap_join(
        col, "jaccard", 0.8, b=32, block=512, return_stats=True)
    dev, dstats = join.blocked_bitmap_join(
        col, "jaccard", 0.8, b=32, block=512, compaction="device",
        return_stats=True)
    assert np.array_equal(oracle, host), (kind, len(oracle), len(host))
    assert np.array_equal(oracle, dev), (kind, len(oracle), len(dev))
    assert hstats == dstats, (hstats, dstats)
    assert 0 < dstats.gather_slots <= dstats.slots, dstats
    assert hstats.gather_slots == 0
    _check_invariants(dstats)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000), simtau=st.sampled_from(SIM_TAUS),
       cap=st.sampled_from((1, 2, 4, 8)))
def test_forced_overflow_escalation_matches_oracle(seed, simtau, cap):
    """Deliberately tiny capacities: overflowing block pairs must be flagged
    and escalated to the dense path without losing a single pair."""
    sim, tau = simtau
    col = _collection("dup_heavy", seed)
    oracle = join.naive_join(col, sim, tau)
    got, stats = join.blocked_bitmap_join(
        col, sim, tau, b=32, block=16, compaction="device", capacity=cap,
        return_stats=True)
    assert np.array_equal(oracle, got), (sim, tau, cap, len(oracle), len(got))
    _check_invariants(stats)
    # Pigeonhole: more candidates than cap × surviving block pairs means at
    # least one block pair overflowed — the flag it claims must be set.
    surviving = stats.blocks_total - stats.blocks_skipped
    if stats.candidates > cap * surviving:
        assert stats.overflow_blocks > 0, stats


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 10_000), simtau=st.sampled_from(SIM_TAUS),
       cap=st.sampled_from((None, 4)))
def test_rs_join_device_resident_matches_oracle(seed, simtau, cap):
    """R×S two-collection joins through the resident path (both prepass-sized
    and forced-overflow capacity)."""
    sim, tau = simtau
    rng = np.random.default_rng(seed)
    col_r = _collection("uniform", seed, n=48)
    sets_s = [rng.choice(110, size=rng.integers(1, 13), replace=False).tolist()
              for _ in range(32)]
    for k in range(4):  # cross-collection duplicates -> non-trivial joins
        sets_s[k] = list(col_r.row(3 * k))
    col_s = from_lists(sets_s, pad_to=_PAD)
    oracle = join.naive_join(col_r, col_s, sim, tau)
    got, stats = join.blocked_bitmap_join(
        col_r, col_s, sim, tau, b=32, block=16, compaction="device",
        capacity=cap, return_stats=True)
    assert np.array_equal(oracle, got), (sim, tau, cap, len(oracle), len(got))
    _check_invariants(stats)


def test_device_path_never_compacts_on_host(monkeypatch):
    """The resident path must not touch the dense host-compaction route
    (``_dense_block_verify`` is the only place a dense verdict tile crosses
    to the host) unless a tile overflows its capacity."""
    col = _collection("uniform", seed=0)
    oracle = join.naive_join(col, "jaccard", 0.6)

    def boom(*a, **kw):
        raise AssertionError("dense host compaction used on the resident path")

    monkeypatch.setattr(join, "_dense_block_verify", boom)
    got = join.blocked_bitmap_join(
        col, "jaccard", 0.6, b=32, block=16, compaction="device")
    assert np.array_equal(oracle, got)
    # ... the host path, by contrast, lives on it:
    with pytest.raises(AssertionError, match="dense host compaction"):
        join.blocked_bitmap_join(col, "jaccard", 0.6, b=32, block=16)


def test_joinstats_json_roundtrip():
    _, stats = join.blocked_bitmap_join(
        _collection("dup_heavy", seed=3), "jaccard", 0.7, b=32, block=16,
        compaction="device", return_stats=True)
    d = stats.to_dict()
    import json
    parsed = json.loads(json.dumps(d))
    assert parsed["candidates"] == stats.candidates
    assert parsed["filter_ratio"] == pytest.approx(stats.filter_ratio)
    assert set(parsed) >= {"total_pairs", "candidates", "verified_true",
                           "overflow_blocks", "filter_ratio", "precision"}


def test_invalid_compaction_mode_rejected():
    with pytest.raises(ValueError, match="compaction"):
        join.blocked_bitmap_join(_collection("uniform", 1), "jaccard", 0.8,
                                 compaction="gpu")


@pytest.mark.parametrize("sim,tau", [("jaccard", 0.8), ("jaccard", 0.9),
                                     ("dice", 0.8), ("cosine", 0.75)])
def test_exactly_at_threshold_pairs_agree_across_drivers(sim, tau):
    """Subset pairs whose similarity sits exactly on (or within float ulps
    of) tau: every driver must return the float64 oracle's verdict.

    Regression for the f32-acceptance bug where r=range(28) ⊂ s=range(35)
    at Jaccard 0.8 got three different answers from naive / blocked /
    indexed (device float32 re-derivation of the Table 1 threshold flips
    membership on boundaries; acceptance now goes through the integer
    ``bounds.min_overlap_table``)."""
    from repro.index import indexed_bitmap_join

    sets = []
    for n in range(2, 40):
        base = list(range(1000 + n * 60, 1000 + n * 60 + n))
        sets.append(base)
        for extra in (1, 2, 3, 7):
            sets.append(base + list(range(7000 + n * 60, 7000 + n * 60 + extra)))
    col = from_lists(sets)
    oracle = join.naive_join(col, sim, tau)
    host = join.blocked_bitmap_join(col, sim, tau, b=32, block=32)
    dev = join.blocked_bitmap_join(col, sim, tau, b=32, block=32,
                                   compaction="device")
    idx = indexed_bitmap_join(col, sim, tau, b=32, probe_block=32)
    assert np.array_equal(oracle, host)
    assert np.array_equal(oracle, dev)
    assert np.array_equal(oracle, idx)
