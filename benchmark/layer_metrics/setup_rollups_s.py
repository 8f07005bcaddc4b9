"""Seconds of set-up spent fetching column rollups (mean, sigma, … a
column; ``frame/rollups.prefetch_rollups``) for the frame's first design
matrix: own seconds of the program's ``frame.rollups`` spans, opened for
a fetch that is really made — a column's rollups are kept."""

from benchmark.layer_metrics.setup_parts import own_seconds


def read(r):
    return own_seconds(r, "frame.rollups")
