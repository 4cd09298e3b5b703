"""Share of candidate pairs the bitmap filter lets through to exact
verification: ``candidates / candidates_generated`` from ``JoinStats``,
summed over the window's joins."""


def read(run):
    stats = getattr(run, "join_stats", None)
    if not stats:
        return None
    generated = sum(s.candidates_generated for s in stats)
    if generated == 0:
        return None
    return 100.0 * sum(s.candidates for s in stats) / generated
