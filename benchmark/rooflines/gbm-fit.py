"""What one whole GBM fit needs by the algorithm, from shapes alone.

Per tree and level every row is read once: its ``features`` bin ids
(``bin_bytes`` each), gradient, hessian and node id (4 bytes each), and
three accumulations per row-feature (rows, g, h into the row's bin).
The same count whatever implements it: one-hot products, scatter-adds
or a kernel all pay at least this."""


def work(s):
    passes = s["ntrees"] * s["max_depth"]
    per_row_bytes = s["features"] * s["bin_bytes"] + 3 * 4
    return {"bytes": passes * s["rows"] * per_row_bytes,
            "flops": passes * s["rows"] * s["features"] * 3}
