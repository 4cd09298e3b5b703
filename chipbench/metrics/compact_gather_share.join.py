"""Share of the blocked driver's compaction slots whose tile compaction
took the lookup form, summed over the window's joins:
``JoinStats.gather_slots`` over ``JoinStats.slots``.  A program without
the ``gather_slots`` counter, or a window that dispatched no step, reads
nothing."""


def read(run):
    stats = getattr(run, "join_stats", None)
    if not stats or not all(hasattr(s, "gather_slots") for s in stats):
        return None
    slots = sum(getattr(s, "slots", 0) for s in stats)
    if slots == 0:
        return None
    return 100.0 * sum(s.gather_slots for s in stats) / slots
