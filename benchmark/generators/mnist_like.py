"""Seeded columns in the MNIST shape: 784 integer pixel columns 0…255
(``C1`` … ``C784``, a 28 x 28 image row by row) and a ten-level
categorical ``label``.

No file can be fetched here, so the pixel statistics are this file's,
not the images' (the configuration says so under ``assumed``). What is
kept of the real data is what the fit's code paths see: integer columns
that narrow to int16, about four fifths of the values zero, and the
outer ring of pixels zero in every row — constant columns, which the
program's standardisation has to turn into zeros and not into NaNs.

The task: ten class prototypes from the seed, each a few soft strokes
on the 26 x 26 interior. A row is its class's prototype blended with a
second class's (``a·P[c] + (1−a)·P[c2]``, ``a`` uniform in
[``MIX_LO``, 1]: rows near ``MIX_LO`` are hard), scaled by a brightness,
plus uniform pixel noise, cut to zero under ``FLOOR`` and quantised. A
``[200, 200]`` network learns it in ten epochs to an error of a few
percent, which the plain reference reports (``_ref_error``).

Rows are made in ``CHUNKS`` independent streams spawned from the seed (a
fixed number, so the data does not depend on the machine's cores), a few
threads at a time, in blocks of ``BLOCK`` rows as float32 and stored as
uint8: the host never holds the frame in a wider type. Each column is a
contiguous row of one ``[784, rows]`` uint8 matrix.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

RESPONSE = "label"
SIDE, CLASSES = 28, 10
PIXELS = SIDE * SIDE
NAMES = tuple(f"C{j + 1}" for j in range(PIXELS))
LEVELS = [str(c) for c in range(CLASSES)]
CHUNKS, THREADS, BLOCK = 16, 8, 1024
STROKES, WIDTH = 3, 1.2         # a prototype: soft strokes, their spread
MIX_LO = 0.48                   # least share of a row's own prototype
NOISE, FLOOR = 72.0, 64.0       # ± half-range of pixel noise; cut-off


def prototypes(seed: int) -> np.ndarray:
    """``[CLASSES, PIXELS]`` float32 in 0…255, zero on the outer ring."""
    r = np.random.default_rng(np.random.SeedSequence([int(seed), 0xD16]))
    yy, xx = np.mgrid[0:SIDE, 0:SIDE].astype(np.float32)
    P = np.zeros((CLASSES, SIDE, SIDE), np.float32)
    for c in range(CLASSES):
        for _ in range(STROKES):
            (y0, x0), (y1, x1) = r.uniform(4, SIDE - 5, (2, 2))
            for t in np.linspace(0.0, 1.0, 12, dtype=np.float32):
                cy, cx = y0 + t * (y1 - y0), x0 + t * (x1 - x0)
                P[c] = np.maximum(P[c], np.exp(
                    -((yy - cy) ** 2 + (xx - cx) ** 2) / (2 * WIDTH ** 2)))
    P *= 255.0
    P[:, 0, :] = P[:, -1, :] = P[:, :, 0] = P[:, :, -1] = 0.0
    return P.reshape(CLASSES, PIXELS)


def _fill(M: np.ndarray, label: np.ndarray, P: np.ndarray, inside,
          lo: int, hi: int, seq) -> None:
    r = np.random.default_rng(seq)
    for a0 in range(lo, hi, BLOCK):
        a1 = min(a0 + BLOCK, hi)
        n = a1 - a0
        c = r.integers(0, CLASSES, n, dtype=np.int32)
        c2 = r.integers(0, CLASSES, n, dtype=np.int32)
        a = (MIX_LO + (1 - MIX_LO) * r.random(n, dtype=np.float32))[:, None]
        gain = (0.7 + 0.3 * r.random(n, dtype=np.float32))[:, None]
        img = (a * P[c] + (1 - a) * P[c2]) * gain
        noise = r.integers(0, 256, (n, PIXELS), dtype=np.uint8)
        img += (noise.astype(np.float32) - 127.5) * np.float32(NOISE / 127.5)
        img[img < FLOOR] = 0.0
        np.minimum(img, 255.0, out=img)
        img *= inside                    # the ring stays zero
        M[:, a0:a1] = img.astype(np.uint8).T
        label[a0:a1] = c


def generate(seed: int, rows: int) -> dict:
    """``{"columns": {name: array}, "domains": {name: levels},
    "response": name}`` for ``rows`` rows from ``seed`` (any whole
    number: ``SeedSequence`` takes it unreduced)."""
    P = prototypes(seed)
    inside = np.zeros((SIDE, SIDE), np.float32)
    inside[1:-1, 1:-1] = 1.0
    inside = inside.reshape(PIXELS)
    M = np.empty((PIXELS, rows), np.uint8)
    label = np.empty(rows, np.int32)
    seqs = np.random.SeedSequence([int(seed), 0xD17]).spawn(CHUNKS)
    cuts = np.linspace(0, rows, CHUNKS + 1).astype(np.int64)
    with ThreadPoolExecutor(THREADS) as pool:
        for f in [pool.submit(_fill, M, label, P, inside, int(cuts[i]),
                              int(cuts[i + 1]), seqs[i])
                  for i in range(CHUNKS)]:
            f.result()
    columns = {name: M[j] for j, name in enumerate(NAMES)}
    columns[RESPONSE] = label
    return {"columns": columns, "domains": {RESPONSE: list(LEVELS)},
            "response": RESPONSE}
