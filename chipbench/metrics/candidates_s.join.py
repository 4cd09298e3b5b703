"""Device seconds of candidate generation per join, from the trace: the
program's ``expand`` (postings expansion and entry filters), ``dedup``
(sort-unique of the candidate entries) and ``window`` (the length-window
mask) stages."""

from chipbench import stages


def read(run):
    return stages.seconds_per_join(run, ("expand", "dedup", "window"))
