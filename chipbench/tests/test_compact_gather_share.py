"""The ``compact_gather_share.join`` reader: the share of the blocked
driver's compaction slots that took the lookup form, from ``JoinStats``
counters, or nothing where the program has no ``gather_slots`` counter."""

import os
from types import SimpleNamespace

import pytest

from chipbench import harness
from chipbench.harness import Record

READ = harness.load_module(os.path.join(
    harness.BENCH_DIR, "metrics", "compact_gather_share.join.py")).read


def _stats(slots, gather_slots=None):
    s = SimpleNamespace(slots=slots, candidates=0, postings_expanded=0)
    if gather_slots is not None:
        s.gather_slots = gather_slots
    return s


@pytest.mark.parametrize("joins,share", [
    ([_stats(4096, 4096)], 100.0),
    ([_stats(4096, 4096), _stats(8192, 2048)], 50.0),
    ([_stats(128, 0)], 0.0),
    # The parent program's stats have no gather_slots counter.
    ([_stats(4096)], None),
    ([_stats(4096, 4096), _stats(4096)], None),
    # No step dispatched, or no joins at all.
    ([_stats(0, 0)], None),
    ([], None),
    (None, None),
])
def test_compact_gather_share(joins, share):
    run = Record() if joins is None else Record(join_stats=joins)
    got = READ(run)
    assert got == (None if share is None else pytest.approx(share))


def test_compact_gather_share_reads_join_stats():
    from repro.core.join import JoinStats

    joins = [JoinStats(slots=1024, gather_slots=768),
             JoinStats(slots=1024, gather_slots=0)]
    assert READ(Record(join_stats=joins)) == pytest.approx(37.5)
