"""The plain reference of the nearest-center cells, and the work of one
nearest-center assignment counted from its shapes.

`reference.assignment_gaps` first merges centers closer than
`SAME_CENTER` into one point, comparing every pair of centers: a
(C, C, d) float64 array, 4.4 TB at C = 65,536.  `nearest_gaps` needs no
such merge: it finds each row's nearest center in float64, blocked over
centers, and counts an answer right where it names that center or one
within `SAME_CENTER` of it.  It imports nothing of the program.

`nearest_flops` and `nearest_bytes` count the algorithm at the unpadded
(N, C, d), whatever implements it: the cross term, the squared norms
and three operations per (row, center) for the distance and the running
minimum; X and the centers read once, one int32 label written per row.
"""
from __future__ import annotations

import numpy as np

from .reference import SAME_CENTER
from .roofline import peaks

BLOCK_CENTERS = 8192      # centers per (rows, block) float64 step


def nearest_gaps(x: np.ndarray, assigned: np.ndarray,
                 centers: np.ndarray,
                 block: int = BLOCK_CENTERS) -> np.ndarray:
    """Per row: ``(d2[given] − d2[nearest]) / (|x|² + |v_nearest|²)`` in
    float64, 0 where the given center lies within `SAME_CENTER` of the
    nearest one (the nearest itself, or an alias of it)."""
    x64 = np.asarray(x, np.float64)
    c64 = np.asarray(centers, np.float64)
    x2 = (x64 * x64).sum(1)
    c2 = (c64 * c64).sum(1)
    low = np.full(len(x64), np.inf)
    best = np.zeros(len(x64), np.int64)
    for s in range(0, len(c64), block):
        d2 = x2[:, None] + c2[None, s:s + block] - 2.0 * x64 @ c64[
            s:s + block].T
        j = np.argmin(d2, axis=1)
        dj = d2[np.arange(len(x64)), j]
        better = dj < low          # strict: earlier blocks keep ties
        best = np.where(better, s + j, best)
        low = np.where(better, dj, low)
    given = np.asarray(assigned, np.int64)
    d2_given = x2 + c2[given] - 2.0 * (x64 * c64[given]).sum(1)
    alias = np.linalg.norm(c64[given] - c64[best], axis=1) <= SAME_CENTER
    gap = (d2_given - low) / (x2 + c2[best])
    return np.where(alias, 0.0, gap)


def nearest_flops(n: int, c: int, d: int) -> float:
    """FLOPs of assigning ``n`` rows to the nearest of ``c`` centers."""
    return (2.0 * n * c * d + 2.0 * n * d + 2.0 * c * d
            + 3.0 * n * c)


def nearest_bytes(n: int, c: int, d: int) -> float:
    """Least HBM traffic: X and the centers read once (float32), one
    int32 label written per row."""
    return 4.0 * (n * d + c * d + n)


def nearest_least_s(n: int, c: int, d: int, device_kind: str) -> float:
    """The least time the chip could take: the larger of FLOPs over peak
    FLOP/s and bytes over peak HBM bytes/s."""
    p = peaks(device_kind)
    return max(nearest_flops(n, c, d) / p["flops_per_s"],
               nearest_bytes(n, c, d) / p["hbm_bytes_per_s"])
