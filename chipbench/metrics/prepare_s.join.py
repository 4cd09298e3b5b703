"""Host seconds of one join's prepare: length sort, upload, bitmap words
and (indexed driver) the postings CSR, ending in ``block_until_ready``;
the mean over the window's joins."""


def read(run):
    times = getattr(run, "prepare_s", None)
    return sum(times) / len(times) if times else None
