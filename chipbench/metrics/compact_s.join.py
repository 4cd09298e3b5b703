"""Device seconds of the program's ``compact`` stage per join, from the
trace: ``nonzero`` into the fixed-capacity buffers and the pair stack."""

from chipbench import stages


def read(run):
    return stages.seconds_per_join(run, ("compact",))
