"""Readers shared by per-layer metrics of the same quantity in different
cells: each ``metrics/<name>.py`` binds one of these as its ``read``."""

from __future__ import annotations

from chipbench import roofline

VERDICT_LAYER = "bitmap_verdict"


def device_idle(run):
    """Share of the traced window in which no operation ran on the device:
    ``100 * (1 - busy / window)``."""
    t = run.trace
    if t is None or t["window_s"] <= 0 or t["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def verdict_roofline(run, name: str, pairs: int, rows: int):
    """Least time of the bitmap verdict on ``pairs`` pairs with ``rows``
    bitmap rows read once, over the verdict kernels' device time, in
    percent; the bound that applies goes to ``run.notes[name]``."""
    if run.trace is None or run.peaks is None:
        return None
    least, bound = roofline.least_time(roofline.verdict_ops(pairs, run.b),
                                       roofline.bitmap_bytes(rows, run.b),
                                       run.peaks)
    kernel_s = run.trace["layers"].get(VERDICT_LAYER)
    share = roofline.share_percent(least, kernel_s)
    if share is not None:
        run.notes[name] = {"bound": bound, "least_s": least,
                           "kernel_s": kernel_s}
    return share
