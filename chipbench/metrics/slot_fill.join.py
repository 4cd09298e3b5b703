"""Share of the device steps' static slots that held work, summed over the
window's joins: ``postings_expanded`` (the indexed driver's entry stream)
or ``candidates`` (the blocked driver's compaction buffer) over
``JoinStats.slots``, the summed capacity of the steps dispatched."""


def read(run):
    stats = getattr(run, "join_stats", None)
    if not stats:
        return None
    slots = sum(getattr(s, "slots", 0) for s in stats)
    if slots == 0:
        return None
    used = sum(s.postings_expanded or s.candidates for s in stats)
    return 100.0 * used / slots
