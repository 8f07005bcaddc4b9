"""One Gram pass of a design held as codes, from shapes alone: every row
is read once — its ``factors`` codes at the frame's codec
(``code_bytes`` each), its ``numerics`` as float32, its weight and
weighted working response — and makes one accumulation (a multiply and
an add) per pair of its non-zeros, the diagonal included (one indicator
a factor, the numerics, the intercept), and one per non-zero into X'Wz.
The same count whatever implements it: a product of whole indicator
blocks, or pieces of the weights, does more arithmetic for the same
sums."""


def work(s):
    k = s["factors"] + s["numerics"] + 1
    per_row = s["factors"] * s["code_bytes"] + 4 * s["numerics"] + 8
    return {"bytes": s["rows"] * per_row,
            "flops": s["rows"] * 2 * (k * (k + 1) // 2 + k)}
