"""The controls: the reference put in the program's place, computed one
precision step below what the configurations state.

The configurations state float32 with every contraction at ``HIGHEST``.
The step below is ``HIGH``: three bf16 passes, which drop the product of
the two low halves.  `dot_high` computes it explicitly, so that the
control reads the same on a TPU and on a CPU (whose float32 dot ignores
the precision flag).

- `ControlScorer` stands in for `repro.serve.Scorer`: nearest center
  (or Eq. 5's memberships) from ‖x‖² + ‖v‖² − 2·x·vᵀ with the cross
  term at ``HIGH``.
- `control_fit` stands in for `repro.core.bigfcm_fit`: plain FCM from
  ``clusters`` rows drawn by the key, every sweep's two contractions at
  ``HIGH``, to the combiner's stopping rule; its objective is the last
  sweep's, over all rows.

A correct check has to find the control out in at least one number of
each cell where the control can differ (see PERF.md for where it cannot).
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def _split(a):
    # reduce_precision, not a cast to bf16 and back: XLA's TPU compiler
    # may drop such a cast pair as excess precision, which would leave
    # the low half zero (one bf16 pass instead of three)
    hi = jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
    lo = jax.lax.reduce_precision(a - hi, exponent_bits=8, mantissa_bits=7)
    return hi, lo


def dot_high(a, b, dims):
    """``dot_general(a, b, dims)`` as ``HIGH`` computes it: hi·hi +
    hi·lo + lo·hi of the bf16 halves (held in float32, where their
    products are exact), accumulated in float32."""
    ah, al = _split(a.astype(jnp.float32))
    bh, bl = _split(b.astype(jnp.float32))

    def dot(x, y):
        return jax.lax.dot_general(x, y, dims, precision=HI,
                                   preferred_element_type=jnp.float32)
    return dot(ah, bh) + dot(ah, bl) + dot(al, bh)


def d2_high(x, v):
    x = x.astype(jnp.float32)
    v = v.astype(jnp.float32)
    cross = dot_high(x, v, (((1,), (1,)), ((), ())))
    return jnp.maximum(jnp.sum(x * x, -1, keepdims=True)
                       + jnp.sum(v * v, -1)[None] - 2.0 * cross, 1e-12)


def _memberships(x, v, m: float):
    """Eq. 5's memberships from `d2_high`, in the log-space form."""
    logd = jnp.log(d2_high(x, v))
    r = jnp.exp(-(logd - jnp.min(logd, -1, keepdims=True)) / (m - 1.0))
    return r / jnp.sum(r, -1, keepdims=True)


class _Snap(NamedTuple):
    version: int
    centers: jax.Array


class ControlScorer:
    """Duck-typed `repro.serve.Scorer`: what `ScoringService` calls."""

    def __init__(self, snapshot, *, m=2.0, soft=False, backend=None,
                 replica="r0"):
        self.replica = str(replica)
        self._snap = _Snap(int(snapshot.version),
                           jnp.asarray(snapshot.centers, jnp.float32))
        self.soft = bool(soft)
        self._fn = jax.jit(partial(_memberships, m=float(m)) if soft else
                           (lambda x, v: jnp.argmin(d2_high(x, v), axis=-1)))
        self.traces = 0

    @property
    def dim(self) -> int:
        return int(self._snap.centers.shape[1])

    @property
    def version(self) -> int:
        return self._snap.version

    def read(self):
        return self._snap

    def score(self, x, snap=None):
        snap = snap or self._snap
        return self._fn(jnp.asarray(x, jnp.float32), snap.centers)


@partial(jax.jit, static_argnames=("m", "eps", "max_iter"))
def _fcm_high(x, v0, m: float, eps: float, max_iter: int):
    def sweep(v):
        d2 = d2_high(x, v)
        r = d2 ** (-1.0 / (m - 1.0))
        um = (r / jnp.sum(r, 1, keepdims=True)) ** m
        v_num = dot_high(um, x, (((0,), (0,)), ((), ())))
        w_i = um.sum(0)
        return v_num / jnp.maximum(w_i, 1e-12)[:, None], w_i, \
            jnp.sum(um * d2)

    def cond(s):
        v, v_prev, it = s
        return (it < max_iter) & ((it == 0) | (
            jnp.max(jnp.sum((v - v_prev) ** 2, -1)) > eps))

    def body(s):
        v, _, it = s
        return sweep(v)[0], v, it + 1

    v, _, it = jax.lax.while_loop(cond, body, (v0, v0, jnp.int32(0)))
    _, w, q = sweep(v)
    return v, w, q, it


class _Diag(NamedTuple):
    flag: bool
    t_fcm_driver: float
    t_wfcmpb_driver: float
    combiner_iters: jax.Array
    reducer_iters: jax.Array


class _Result(NamedTuple):
    centers: jax.Array
    center_weights: jax.Array
    objective: jax.Array
    diagnostics: _Diag


def control_fit(x, cfg, *, mesh=None, key=None, **_):
    """Duck-typed `repro.core.bigfcm_fit`: FCM at ``HIGH`` over all
    rows, on one device."""
    x = jnp.asarray(x, jnp.float32)
    idx = jax.random.choice(key, x.shape[0], (cfg.n_clusters,),
                            replace=False)
    v, w, q, it = _fcm_high(x, jnp.take(x, idx, axis=0), float(cfg.m),
                            float(cfg.combiner_eps), int(cfg.max_iter))
    return _Result(v, w, q, _Diag(True, 0.0, 0.0, it[None], np.int32(0)))
