"""Host seconds of a frame's first binning before the device is asked
for anything: own seconds of the program's ``bin.fetch`` (the numeric
columns and the weights brought to the host) and ``bin.edges`` (the
quantile cuts: a sort of every row of every numeric column) spans,
which ``frame/binning.bin_frame`` opens on a cache miss alone."""

from benchmark.layer_metrics.setup_parts import own_seconds


def read(r):
    return own_seconds(r, "bin.fetch", "bin.edges")
