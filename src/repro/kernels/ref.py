"""Plain oracles for every Pallas kernel in this package: pure jnp for
the sweep, float64 NumPy for nearest-center assignment."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

_D2_FLOOR = 1e-12
SAME_CENTER = 1e-3        # centers closer than this are one point
_BLOCK_ELEMS = 1 << 24    # (rows, centers, d) float64 elements a block


def fcm_accumulate_ref(x, w, centers, m: float = 2.0):
    """Reference raw accumulators (v_num, w_i, q) — oracle for
    ``fcm_accumulate_pallas`` (sweep math with normalization deferred)."""
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    v = centers.astype(jnp.float32)
    d2 = jnp.maximum(
        jnp.sum((x[:, None, :] - v[None, :, :]) ** 2, axis=-1), _D2_FLOOR)
    expo = 1.0 / (m - 1.0)
    logd = jnp.log(d2)
    lmin = jnp.min(logd, axis=-1, keepdims=True)
    r = jnp.exp(-expo * (logd - lmin))
    u = r / jnp.sum(r, axis=-1, keepdims=True)
    wum = jnp.power(u, m) * w[:, None]
    return wum.T @ x, jnp.sum(wum, axis=0), jnp.sum(wum * d2)


def fcm_sweep_ref(x, w, centers, m: float = 2.0):
    """Reference Alg.-1 sweep: returns (v_new, w_i, q) — the accumulate
    oracle plus the one deferred normalization (mirrors the
    ``fcm_sweep_pallas`` / ``fcm_accumulate_pallas`` split)."""
    v_num, w_i, q = fcm_accumulate_ref(x, w, centers, m)
    v_new = v_num / jnp.maximum(w_i, _D2_FLOOR)[:, None]
    return v_new, w_i, q


def nearest_center_ref(x, centers):
    """``(index, d2)``: each row's nearest center and its squared
    distance, in float64 by direct differences ‖x−v‖², blocked over
    centers.  Ties go to the lowest index."""
    x64 = np.asarray(x, np.float64)
    c64 = np.asarray(centers, np.float64)
    n, d = x64.shape
    block = max(1, _BLOCK_ELEMS // max(n * d, 1))
    best = np.zeros(n, np.int64)
    low = np.full(n, np.inf)
    for s in range(0, len(c64), block):
        d2 = ((x64[:, None, :] - c64[None, s:s + block]) ** 2).sum(-1)
        j = np.argmin(d2, axis=1)
        dj = d2[np.arange(n), j]
        better = dj < low          # strict: earlier blocks keep ties
        best = np.where(better, s + j, best)
        low = np.where(better, dj, low)
    return best, low


def nearest_center_right(x, centers, assigned):
    """Per row: whether ``assigned`` names the nearest center or a
    center within `SAME_CENTER` of it (duplicated centers are one
    point, so either of them is right)."""
    best, _ = nearest_center_ref(x, centers)
    c64 = np.asarray(centers, np.float64)
    got = c64[np.asarray(assigned, np.int64)]
    return np.linalg.norm(got - c64[best], axis=1) <= SAME_CENTER
