"""Seeded airline rows with the factor columns of a linear model on the
airline data (github.com/szilard/benchm-ml's linear-model benchmark):
``Month``, ``DayofMonth``, ``DayOfWeek``, ``UniqueCarrier``, ``Origin``,
``Dest`` as factors, ``DepTime`` (hhmm) and ``Distance`` (miles) as
numerics, and the binary ``IsDepDelayed`` response (departure delayed by
15 minutes or more).

``levels`` gives each factor's level count. Airports and carriers are
drawn by popularity, the level of rank ``k`` (from 1) with weight
``k ** -zipf``; ``Month``, ``DayofMonth`` and ``DayOfWeek`` uniformly;
``Origin`` and ``Dest`` independently. The response is logistic in
coefficients drawn once from ``BETA_SEED`` — one per level of each
factor (the first level's is the reference, 0), one per standardised
numeric and an intercept set for a delayed share of about a fifth — the
same for every seed: the seed draws the rows, not the problem, so every
seed brings the same Newton iterations. ``beta`` in the result holds
those coefficients (a factor's first level at 0, the numerics per the
centre and spread ``_fill`` states).

Codes are the narrowest integer type that holds them. Rows are made in
``CHUNKS`` independent streams spawned from the seed (a fixed number, so
the data does not depend on the machine's cores), a few threads at a
time: numpy's generators release the GIL. No value is missing.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

RESPONSE = "IsDepDelayed"
FACTORS = ("Month", "DayofMonth", "DayOfWeek", "UniqueCarrier", "Origin",
           "Dest")
BY_POPULARITY = ("UniqueCarrier", "Origin", "Dest")
# sd of the drawn per-level coefficients of each factor
SPREAD = {"Month": 0.2, "DayofMonth": 0.05, "DayOfWeek": 0.1,
          "UniqueCarrier": 0.3, "Origin": 0.3, "Dest": 0.2}
# per standardised unit: later departures are later, longer flights a little
NUMERIC = {"DepTime": 0.5, "Distance": 0.05}
# departures by hour of the day (0..23): what a schedule looks like
HOURS = np.array([1, 0.5, 0.2, 0.2, 0.3, 2, 6, 7, 7, 6.5, 6, 6, 6.5, 6.5,
                  6, 6, 6.5, 7, 6.5, 6, 5, 4, 3, 2])
BETA_SEED = 0xA1F
CHUNKS = 16
THREADS = 8


def _problem(levels: dict, zipf: float):
    """What every seed shares: popularity tables and coefficients."""
    r = np.random.default_rng(BETA_SEED)
    cdf = {}
    for f in BY_POPULARITY:
        w = np.arange(1, levels[f] + 1, dtype=np.float64) ** -zipf
        # popularity ranks are not code order: a code's rank is drawn
        cdf[f] = np.cumsum(w[r.permutation(levels[f])] / w.sum())
    beta = {f: np.concatenate([[0.0], r.standard_normal(levels[f] - 1)
                               * SPREAD[f]]) for f in FACTORS}
    beta.update(NUMERIC)
    beta["Intercept"] = -1.6
    return cdf, beta


def _dtype(n: int):
    return np.int8 if n <= 127 else np.int16


def _fill(columns, cdf, beta, lo, hi, seq) -> None:
    r = np.random.default_rng(seq)
    n = hi - lo
    eta = np.full(n, beta["Intercept"], np.float64)
    for f in FACTORS:
        L = len(beta[f])
        if f in cdf:
            code = np.minimum(np.searchsorted(cdf[f], r.random(n)), L - 1)
        else:
            code = r.integers(0, L, n)
        columns[f][lo:hi] = code
        eta += beta[f][code]
    hour = np.searchsorted(np.cumsum(HOURS / HOURS.sum()), r.random(n))
    dep = np.minimum(hour, 23) * 100 + r.integers(0, 60, n)
    dist = np.clip(np.exp(r.normal(6.5, 0.6, n)), 30, 4983).astype(np.int64)
    columns["DepTime"][lo:hi] = dep
    columns["Distance"][lo:hi] = dist
    eta += beta["DepTime"] * (dep - 1330.0) / 470.0
    eta += beta["Distance"] * (dist - 730.0) / 560.0
    columns[RESPONSE][lo:hi] = r.random(n) < 1.0 / (1.0 + np.exp(-eta))


def generate(seed: int, rows: int, levels=None, zipf: float = 0.8) -> dict:
    """``{"columns", "domains", "response", "beta"}`` for ``rows`` rows
    from ``seed`` (any whole number: ``SeedSequence`` takes it
    unreduced)."""
    levels = {f: int(levels[f]) for f in FACTORS}
    cdf, beta = _problem(levels, zipf)
    columns = {f: np.empty(rows, _dtype(levels[f])) for f in FACTORS}
    columns["DepTime"] = np.empty(rows, np.int16)
    columns["Distance"] = np.empty(rows, np.int16)
    columns[RESPONSE] = np.empty(rows, np.int8)
    seqs = np.random.SeedSequence([int(seed), 0xA1F]).spawn(CHUNKS)
    cuts = np.linspace(0, rows, CHUNKS + 1).astype(np.int64)
    with ThreadPoolExecutor(THREADS) as pool:
        for f in [pool.submit(_fill, columns, cdf, beta, int(cuts[i]),
                              int(cuts[i + 1]), seqs[i])
                  for i in range(CHUNKS)]:
            f.result()
    domains = {f: [f"{f}{k:03d}" for k in range(levels[f])] for f in FACTORS}
    domains[RESPONSE] = ["NO", "YES"]
    return {"columns": columns, "domains": domains, "response": RESPONSE,
            "beta": beta}
