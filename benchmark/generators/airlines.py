"""Seeded airlines-schema columns (H2O's public airlines benchmark schema).

The arithmetic of ``h2o3_tpu/utils/synth.py`` ``write_airlines_csv``,
producing arrays instead of a CSV (ingest is its own cell later): ten
features — six integer columns, an 8-level carrier, two 125-level
airport codes, a distance — and the binary ``IsDepDelayed`` response
driven by departure time, carrier and month. Categorical columns are
integer codes with their level lists, so no string is ever built.

Rows are made in ``CHUNKS`` independent streams spawned from the seed
(a fixed number, so the data does not depend on the machine's cores),
a few threads at a time: numpy's generators release the GIL.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

RESPONSE = "IsDepDelayed"
CARRIERS = ["AA", "CO", "DL", "MQ", "NW", "UA", "US", "WN"]   # sorted
ORIGINS = [f"{a}{b}{c}" for a in "ABCDE" for b in "AEIOU" for c in "KLMNP"]
LATE_CARRIERS = (4, 5)       # NW, UA: the synth file's +15 minutes
INT_COLUMNS = ("Year", "Month", "DayofMonth", "DayOfWeek", "DepTime",
               "CRSDepTime", "UniqueCarrier", "Origin", "Dest", "Distance")
CHUNKS = 16
THREADS = 8


def _fill(columns: dict, lo: int, hi: int, seq) -> None:
    r = np.random.default_rng(seq)
    n = hi - lo
    i32 = np.int32

    def ints(a, b):
        return r.integers(a, b, n, dtype=i32)

    dep = ints(0, 2400)
    month = ints(1, 13)
    car = ints(0, len(CARRIERS))
    # learnable signal: late-day departures + carrier/month effects
    delay = (np.float32(0.03) * (dep - 1000).astype(np.float32)
             + np.isin(car, LATE_CARRIERS) * np.float32(15)
             + np.isin(month, (12, 1, 6)) * np.float32(8)
             + r.standard_normal(n, dtype=np.float32) * np.float32(25))
    part = {
        "Year": ints(1987, 2009), "Month": month,
        "DayofMonth": ints(1, 29), "DayOfWeek": ints(1, 8),
        "DepTime": dep,
        "CRSDepTime": np.maximum(dep - ints(-10, 60), 0),
        "UniqueCarrier": car, "Origin": ints(0, len(ORIGINS)),
        "Dest": ints(0, len(ORIGINS)), "Distance": ints(50, 2600),
        RESPONSE: delay > 15,
    }
    for name, v in part.items():
        columns[name][lo:hi] = v


def generate(seed: int, rows: int) -> dict:
    """``{"columns": {name: int32 array}, "domains": {name: levels},
    "response": name}`` for ``rows`` rows from ``seed`` (any whole
    number: ``SeedSequence`` takes it unreduced)."""
    columns = {name: np.empty(rows, np.int32)
               for name in INT_COLUMNS + (RESPONSE,)}
    seqs = np.random.SeedSequence([int(seed), 0xA1B]).spawn(CHUNKS)
    cuts = np.linspace(0, rows, CHUNKS + 1).astype(np.int64)
    with ThreadPoolExecutor(THREADS) as pool:
        for f in [pool.submit(_fill, columns, int(cuts[i]),
                              int(cuts[i + 1]), seqs[i])
                  for i in range(CHUNKS)]:
            f.result()
    domains = {"UniqueCarrier": list(CARRIERS), "Origin": list(ORIGINS),
               "Dest": list(ORIGINS), RESPONSE: ["NO", "YES"]}
    return {"columns": columns, "domains": domains, "response": RESPONSE}
