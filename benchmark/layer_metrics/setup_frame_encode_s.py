"""Host seconds of ``Frame.from_numpy`` spent in the passes that pick a
column's codec and pad it (``frame/column.column_from_numpy``): own
seconds of the program's ``frame.encode`` spans, one a column."""

from benchmark.layer_metrics.setup_parts import own_seconds


def read(r):
    return own_seconds(r, "frame.encode")
