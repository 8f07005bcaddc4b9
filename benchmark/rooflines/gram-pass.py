"""One Gram pass, from shapes alone: the float32 design matrix read once
(``rows`` x (``cols`` + 1) x 4 bytes) and 2 * rows * (cols + 1)^2
operations."""


def work(s):
    p1 = s["cols"] + 1
    return {"bytes": s["rows"] * p1 * 4,
            "flops": 2 * s["rows"] * p1 * p1}
