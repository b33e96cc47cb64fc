"""The work of one FCM accumulation sweep, counted from its shapes, and
the chip's peaks.

`sweep_flops` and `sweep_bytes` are copied from the program's analytic
model and count the algorithm at the unpadded (N, C, d), whatever
implements it: padding that a kernel adds is its own cost, not work.
"""
from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "peaks.json")


def sweep_flops(n: int, c: int, d: int) -> float:
    """FLOPs of one sweep: the two (N, C, d) contractions (distance
    cross term and center numerators, 2·N·C·d each), the squared norms,
    and about 14 per (row, center) for distances, memberships (one per
    transcendental) and the three accumulator sums."""
    return (4.0 * n * c * d
            + 2.0 * n * d + 2.0 * c * d
            + 14.0 * n * c)


def sweep_bytes(n: int, c: int, d: int, in_bytes: int = 4) -> float:
    """Least HBM traffic of one sweep: X and the record weights read
    once, the centers read once, the three accumulators written once.
    The (N, C) memberships stay on chip and are not counted."""
    return (n * d * in_bytes + n * in_bytes
            + c * d * in_bytes
            + (c * d + c + 1) * 4.0)


def peaks(device_kind: str, path: str = PEAKS_FILE) -> dict:
    """The peaks of ``device_kind``; a kind not in the table raises."""
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path}; known: {sorted(table)}")
    return table[device_kind]


def sweep_roofline(n: int, c: int, d: int, seconds: float,
                   device_kind: str) -> tuple:
    """``(share in %, bound)``: the least time the chip could take for
    one sweep, the larger of FLOPs over peak FLOP/s and bytes over peak
    bytes/s, over the measured ``seconds``; ``bound`` names the larger."""
    p = peaks(device_kind)
    t_flops = sweep_flops(n, c, d) / p["flops_per_s"]
    t_bytes = sweep_bytes(n, c, d) / p["hbm_bytes_per_s"]
    bound = "bytes" if t_bytes >= t_flops else "flops"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound
