"""One level pass of a tree fit, from shapes alone: every row is read
once — its ``features`` bin ids (``bin_bytes`` each), its three float32
statistics {w, w·g, w·h} and its node id (4 bytes each) — and makes
three accumulations per row-feature (into the row's bin of each
feature). The same count whatever implements it: a kernel that feeds
the statistics to the MXU as several pieces still needs them once."""


def work(s):
    per_row_bytes = s["features"] * s["bin_bytes"] + 3 * 4 + 4
    return {"bytes": s["rows"] * per_row_bytes,
            "flops": s["rows"] * s["features"] * 3}
