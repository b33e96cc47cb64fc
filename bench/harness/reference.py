"""The plain references that decide ``correct``.  They import nothing
of the program and take nothing it made except the answers under test.

- `reference_sweep`: one float32 FCM sweep by direct differences
  ‖x−v‖² (no expansion), Eq. 5 memberships, and row blocks summed in
  order, with every contraction at ``HIGHEST``.
- `fit_readings`: for each fit, how much lower the reference objective
  gets in a few reference sweeps from its centers, how far its centers
  move in one, and how its masses compare with the reference's over
  all rows.
- `assignment_gaps`: for each served row, by how much the squared
  distance to the center it was given exceeds the distance to the
  nearest one, in float64 on the host, over the row's scale.
- `membership_gaps`: for each served row, the largest difference of its
  memberships from Eq. 5's, in float64 on the host.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 65_536            # rows per (block, C, d) step of the reference
SAME_CENTER = 1e-3        # centers closer than this are one point
SWEEPS = 5                # reference sweeps after a fit (converged by 5
#                           on the KDD shape, to 1e-7 of the objective)


@partial(jax.jit, static_argnames=("m", "block"))
def reference_sweep(x, w, centers, m: float = 2.0, block: int = BLOCK):
    """Raw ``(v_num, w_i, q)`` of one plain float32 FCM sweep."""
    n, d = x.shape
    pad = -n % block
    xb = jnp.pad(x, ((0, pad), (0, 0))).reshape(-1, block, d)
    wb = jnp.pad(w, (0, pad)).reshape(-1, block)

    def step(carry, xw):
        xi, wi = xw
        d2 = jnp.sum((xi[:, None, :] - centers[None]) ** 2, axis=-1)
        d2 = jnp.maximum(d2, 1e-12)
        r = d2 ** (-1.0 / (m - 1.0))
        um = (r / jnp.sum(r, axis=1, keepdims=True)) ** m * wi[:, None]
        v_num, w_i, q = carry
        return (v_num + um.T @ xi, w_i + um.sum(0),
                q + jnp.sum(um * d2)), None

    c = centers.shape[0]
    init = (jnp.zeros((c, d), jnp.float32), jnp.zeros((c,), jnp.float32),
            jnp.float32(0.0))
    with jax.default_matmul_precision("highest"):
        out, _ = jax.lax.scan(step, init, (xb, wb))
    return out


def fit_readings(x, fits, m: float, sweeps: int = SWEEPS):
    """For each ``(centers, masses)`` of ``fits``: a dict of

    - ``gap``: how much lower the reference objective gets in
      ``sweeps`` reference sweeps from the centers, relative,
      ``(q − q_after) / q_after`` (about 0 at a local optimum of FCM
      over all of ``x``);
    - ``shift``: the farthest a center moves in the first of those
      sweeps, over the RMS norm of the rows (about 0 at a fixed point,
      to the fit's stopping rule);
    - ``mass``: ``|Σ masses / Σ w_ref − 1|``, where ``w_ref = Σ_k u^m``
      is the reference's mass of each center over all rows (the fit's
      masses are Eq. 6's, over every row it clustered)."""
    w = jnp.ones((x.shape[0],), jnp.float32)
    rms = float(jnp.sqrt(jnp.mean(jnp.sum(
        x.astype(jnp.float32) ** 2, axis=1))))
    out = []
    for centers, masses in fits:
        v = jnp.asarray(centers, jnp.float32)
        qs = []
        for s in range(sweeps + 1):
            v_num, w_i, q = reference_sweep(x, w, v, m)
            qs.append(float(q))
            v_next = v_num / jnp.maximum(w_i, 1e-12)[:, None]
            if s == 0:
                shift = float(jnp.max(jnp.linalg.norm(v_next - v, axis=1)))
                w_ref = float(jnp.sum(w_i.astype(jnp.float32)))
            v = v_next
        got = float(np.sum(np.asarray(masses, np.float64)))
        out.append({"gap": (qs[0] - qs[-1]) / qs[-1], "shift": shift / rms,
                    "mass": abs(got / w_ref - 1.0)})
    return out


def center_points(centers: np.ndarray):
    """``(point, distinct)``: each center's first alias among centers
    closer than `SAME_CENTER`, and the indices of the distinct points.
    An answer that names any center of the right point is right."""
    c64 = np.asarray(centers, np.float64)
    gap = np.linalg.norm(c64[:, None] - c64[None], axis=-1)
    point = np.argmax(gap <= SAME_CENTER, axis=1)
    return point, np.unique(point)


def assignment_gaps(x: np.ndarray, assigned: np.ndarray,
                    centers: np.ndarray) -> np.ndarray:
    """Per row: ``(d2[given point] − d2[nearest point]) / (|x|² +
    |v_nearest|²)`` in float64, 0 where the answer is a nearest point.
    A wrong answer on a near-tie gives a gap at rounding size; a wrong
    answer elsewhere gives a large one."""
    c64 = np.asarray(centers, np.float64)
    point, distinct = center_points(c64)
    x64 = np.asarray(x, np.float64)
    cd = c64[distinct]
    d2 = ((x64 * x64).sum(1)[:, None] + (cd * cd).sum(1)[None]
          - 2.0 * x64 @ cd.T)
    col = np.full(len(c64), -1, np.int64)
    col[distinct] = np.arange(len(distinct))
    given = col[point[np.asarray(assigned, np.int64)]]
    rows = np.arange(len(x64))
    best = np.argmin(d2, axis=1)
    scale = (x64 * x64).sum(1) + (cd[best] ** 2).sum(1)
    return (d2[rows, given] - d2[rows, best]) / scale


def membership_gaps(x: np.ndarray, got: np.ndarray, centers: np.ndarray,
                    m: float) -> np.ndarray:
    """Per row: ``max_i |u_i − u_ref_i|``, where ``u_ref`` are Eq. 5's
    memberships of the row in each center, from float64 distances
    (floored at 1e-12, a row on a center belonging to it)."""
    c64 = np.asarray(centers, np.float64)
    x64 = np.asarray(x, np.float64)
    d2 = np.maximum(((x64[:, None, :] - c64[None]) ** 2).sum(-1), 1e-12)
    r = np.exp(-(np.log(d2) - np.log(d2).min(1, keepdims=True))
               / (m - 1.0))
    want = r / r.sum(1, keepdims=True)
    return np.abs(np.asarray(got, np.float64) - want).max(1)
