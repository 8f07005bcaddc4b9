"""What one whole random-forest fit needs by the algorithm, from shapes
alone.

Per tree and level every row is read once: its ``features`` bin ids
(``bin_bytes`` each), its weight, its response and its node id (4 bytes
each), and three accumulations per row-feature (rows, responses, rows
again as the Newton denominator, into the row's bin) — ``ntrees`` x
``max_depth`` passes a job. The same count whatever implements a pass
(one-hot products over a complete level, rows ordered by node and summed
by block): ordering the rows is this implementation's cost, not the
algorithm's."""


def work(s):
    passes = s["ntrees"] * s["max_depth"]
    per_row_bytes = s["features"] * s["bin_bytes"] + 3 * 4
    return {"bytes": passes * s["rows"] * per_row_bytes,
            "flops": passes * s["rows"] * s["features"] * 3}
