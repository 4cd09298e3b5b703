"""Share of the indexed driver's probe chunks (or the blocked driver's
block pairs) escalated to the dense fallback: ``overflow_blocks /
blocks_total`` from ``JoinStats``, summed over the window's joins."""


def read(run):
    stats = getattr(run, "join_stats", None)
    if not stats:
        return None
    total = sum(s.blocks_total for s in stats)
    if total == 0:
        return None
    return 100.0 * sum(s.overflow_blocks for s in stats) / total
