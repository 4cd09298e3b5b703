"""Roofline share of the bitmap verdict kernels over the window's joins.

Kernel time: the device time of the operations that
``layers/bitmap_verdict.json`` maps to the layer, from the trace.  Least
time: the larger of the verdict's operations on the pairs it was consumed
on (``JoinStats.total_pairs``, 2 b int8 operations each) over the int8
peak, and every bitmap row read once per join over HBM bandwidth.
"""

from chipbench.readers import verdict_roofline

LAYER_FILE = "bitmap_verdict"


def read(run):
    stats = getattr(run, "join_stats", None)
    if not stats:
        return None
    return verdict_roofline(run, "bitmap_verdict_roofline.join",
                            sum(s.total_pairs for s in stats),
                            run.joins * run.n_sets)
