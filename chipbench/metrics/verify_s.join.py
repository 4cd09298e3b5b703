"""Device seconds of exact verification per join, from the trace: the
program's ``verify`` stage (gathered overlap, minimum-overlap lookup and the
accept)."""

from chipbench import stages


def read(run):
    return stages.seconds_per_join(run, ("verify",))
