"""Seeded synthetic data for ``chip_smoke.py``.

Not a dataset loader: the files written here exist so that the ingest
path (streaming CSV → device) has real on-disk bytes of a known schema
to parse, with a learnable signal for the fits that follow.
"""

from __future__ import annotations

import os

import numpy as np

AIRLINES_RESPONSE = "IsDepDelayed"


def write_airlines_csv(path: str, n_rows: int, seed: int) -> str:
    """Write an airlines-schema CSV of ``n_rows`` to ``path``: ten
    features (six integer columns, an 8-level carrier, two 125-level
    airport codes, a distance) and the binary ``IsDepDelayed`` response
    driven by departure time, carrier and month."""
    r = np.random.RandomState(seed)
    carriers = np.array(["UA", "AA", "DL", "WN", "US", "NW", "CO", "MQ"])
    origins = np.array([f"{a}{b}{c}" for a in "ABCDE" for b in "AEIOU"
                        for c in "KLMNP"])
    # pyarrow csv writer over dictionary-encoded string columns: the
    # strings are never materialized host-side (~80 MB/s vs ~6 for
    # object arrays) — a 50M-row (2.4GB) file must not eat a bench
    # budget in generation
    import pyarrow as pa
    import pyarrow.csv as pacsv

    def _dict(idx, values):
        return pa.DictionaryArray.from_arrays(
            pa.array(idx, type=pa.int32()), pa.array(list(values)))

    chunk = 2_000_000
    writer = None
    with open(path + ".tmp", "wb") as sink:
        for lo in range(0, n_rows, chunk):
            n = min(chunk, n_rows - lo)
            dep = r.randint(0, 2400, n)
            crs = np.maximum(dep - r.randint(-10, 60, n), 0)
            month = r.randint(1, 13, n)
            car_i = r.randint(0, len(carriers), n)
            # learnable signal: late-day departures + carrier/month effects
            delay = (0.03 * (dep - 1000)
                     + np.isin(car_i, [0, 5]) * 15          # UA, NW
                     + np.isin(month, [12, 1, 6]) * 8
                     + r.randn(n) * 25)
            cols = {
                "Year": pa.array(r.randint(1987, 2009, n)),
                "Month": pa.array(month),
                "DayofMonth": pa.array(r.randint(1, 29, n)),
                "DayOfWeek": pa.array(r.randint(1, 8, n)),
                "DepTime": pa.array(dep),
                "CRSDepTime": pa.array(crs),
                "UniqueCarrier": _dict(car_i, carriers),
                "Origin": _dict(r.randint(0, len(origins), n), origins),
                "Dest": _dict(r.randint(0, len(origins), n), origins),
                "Distance": pa.array(r.randint(50, 2600, n)),
                AIRLINES_RESPONSE: _dict((delay > 15).astype(np.int32),
                                         ["NO", "YES"]),
            }
            tbl = pa.table(cols)
            if writer is None:
                writer = pacsv.CSVWriter(sink, tbl.schema)
            writer.write_table(tbl)
        writer.close()
    os.rename(path + ".tmp", path)
    return path
