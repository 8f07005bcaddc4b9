"""What one whole IRLS fit needs by the algorithm, from shapes alone:
per iteration one pass over the float32 design matrix (``rows`` x
(``cols`` + 1)) and the Gram product's 2 * rows * (cols + 1)^2
operations. ``passes`` are the iterations the solve ran."""


def work(s):
    p1 = s["cols"] + 1
    return {"bytes": s["passes"] * s["rows"] * p1 * 4,
            "flops": s["passes"] * 2 * s["rows"] * p1 * p1}
