"""Host seconds of set-up spent in the one warm-up job: compile-cache
loads or compiles, first binning / design matrix, and one whole fit."""


def read(r):
    return r.setup_seconds["setup_warmup"]
