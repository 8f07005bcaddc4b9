"""What one whole IRLS fit on a design held as codes needs by the
algorithm, from shapes alone: per iteration one Gram pass
(``cat-gram-pass.py``: every row's codes, numerics, weight and weighted
response read once, one accumulation per pair of its non-zeros).
``passes`` are the iterations the solve needs, counted by the plain
reference."""


def work(s):
    k = s["factors"] + s["numerics"] + 1
    per_row = s["factors"] * s["code_bytes"] + 4 * s["numerics"] + 8
    return {"bytes": s["passes"] * s["rows"] * per_row,
            "flops": s["passes"] * s["rows"] * 2 * (k * (k + 1) // 2 + k)}
