"""Seeded HIGGS-shape columns: ``cols`` float32 Gaussian features and a
logistic binary response (the arithmetic of ``bench.py`` ``bench_glm``:
``beta ~ 0.3 N(0, 1)``, ``y ~ Bernoulli(sigmoid(X beta))``). Features
are column-major, so each column is contiguous for the upload. Rows are
made in ``CHUNKS`` independent streams spawned from the seed (a fixed
number, so the data does not depend on the machine's cores).

``levels`` (column index → ``a``) makes that column one of the source's
jet b-tag columns instead: three levels ``0, a, 2a`` (the source's own
values), with shares ``1/2, 1 - 1/a, 1/a - 1/2`` assumed so that the
column's mean is 1, as the source normalises it. A level is a value every
row of it shares, so rounding it (a design matrix held in bfloat16) moves
a coefficient by the rounding itself, where rounding a continuous column
averages out over the rows.

``beta`` is the same for every seed (``BETA_SEED``): how many IRLS
iterations a fit needs depends on it, and every seed has to bring the
same work — the seed draws the rows, not the problem.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

RESPONSE = "y"
BETA_SEED = 0x4165
CHUNKS = 16
THREADS = 8


def _fill(Xt, y, beta32, levels: dict, lo: int, hi: int, seq) -> None:
    r = np.random.default_rng(seq)
    n = hi - lo
    for i in range(Xt.shape[0]):
        if i in levels:
            a = np.float32(levels[i])
            u = r.random(n, dtype=np.float32)
            Xt[i, lo:hi] = a * ((u > 0.5).astype(np.float32)
                                + (u > 1.5 - 1.0 / a))
        else:
            Xt[i, lo:hi] = r.standard_normal(n, dtype=np.float32)
    eta = beta32 @ Xt[:, lo:hi]
    y[lo:hi] = r.random(n, dtype=np.float32) < 1.0 / (1.0 + np.exp(-eta))


def generate(seed: int, rows: int, cols: int = 28, levels=None) -> dict:
    levels = {int(k): float(v) for k, v in (levels or {}).items()}
    seqs = np.random.SeedSequence([int(seed), 0x4165]).spawn(CHUNKS)
    beta = np.random.default_rng(BETA_SEED).standard_normal(cols) * 0.3
    Xt = np.empty((cols, rows), np.float32)
    y = np.empty(rows, np.int32)
    cuts = np.linspace(0, rows, CHUNKS + 1).astype(np.int64)
    with ThreadPoolExecutor(THREADS) as pool:
        for f in [pool.submit(_fill, Xt, y, beta.astype(np.float32), levels,
                              int(cuts[i]), int(cuts[i + 1]), seqs[i])
                  for i in range(CHUNKS)]:
            f.result()
    columns = {f"x{i}": Xt[i] for i in range(cols)}
    columns[RESPONSE] = y
    return {"columns": columns, "domains": {RESPONSE: ["b", "s"]},
            "response": RESPONSE, "beta": beta}
