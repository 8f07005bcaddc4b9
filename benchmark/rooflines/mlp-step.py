"""One mini-batch step of a dense-layer fit, from shapes alone.

Operations: the forward products 2·B·Σ fan_in·fan_out over the layers,
the weight gradients the same again, and the input gradients
2·B·fan_in·fan_out of every layer but the first (nothing flows back into
the data). Bytes: the batch's ``B`` rows of ``inputs`` float32 values
read once, and every weight and bias with its two ADADELTA moments read
and written once (3 arrays x 2 x 4 bytes). The same count whatever
implements it, and per step that takes effect: a step computed and
thrown away is no work the algorithm needs."""


def sizes(s):
    return [s["inputs"]] + list(s["hidden"]) + [s["classes"]]


def work(s):
    z = sizes(s)
    pairs = [a * b for a, b in zip(z[:-1], z[1:])]
    weights = sum(pairs) + sum(z[1:])
    flops = 2 * s["batch"] * (2 * sum(pairs) + sum(pairs[1:]))
    return {"flops": flops,
            "bytes": s["batch"] * s["inputs"] * 4 + weights * 3 * 2 * 4}
