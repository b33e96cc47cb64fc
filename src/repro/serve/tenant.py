"""Tenant-routed scoring — one gather-scored launch for cross-tenant
traffic.

A fleet of per-tenant models (`repro.tenant.TenantSet`) served naively
is one scorer per tenant: T compiled programs, T dispatches, and a
coalescing queue per tenant that never fills.  This module keeps ONE
service over the whole fleet:

  * `TenantSnapshot` — the immutable published fleet: stacked (T, C, d)
    centers on device, per-tenant ``versions``, and the id→row index.
    Hot-swap is the same one-attribute-store never-tear rule as
    `Scorer`: each dispatched batch reads the snapshot exactly once, so
    every response is scored against exactly ONE version of its tenant.
  * `TenantScorer` — the jitted gather-score: requests from different
    tenants coalesce into one (B, d) batch with a (B,) tenant-row
    vector; the program gathers each row's centers
    (``centers[tidx]``) and scores all tenants in ONE launch.  Compiled
    once per (batch bucket, T, C) shape — cross-tenant traffic shares
    programs instead of multiplying them.
  * `TenantScoringService` — `ScoringService` with tenant routing:
    ``submit(tenant, x)`` tags the request with its tenant id (also the
    fairness group — set ``ServiceConfig.max_group_rows`` so a hot
    tenant cannot starve a quiet one), and its `_pack` pads
    cross-tenant batches onto the same bucket ladder; the base
    service's worker loop launches and answers them.

Observability: the service's batches run under the base service's
``serve.*`` spans and counters; the single-shot `TenantScorer.assign`
runs under ``span.tenant.assign{tenants=1}``.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.data.plane import pad_rows
from repro.engine.backend import _u_from_d2
from repro.tenant.core import TenantSet

from .service import ScoreResult, ScoringService, ServiceConfig, _Batch


class TenantSnapshot(NamedTuple):
    """One immutable published tenant fleet (the never-tear unit)."""
    ids: Tuple[str, ...]          # (T,) tenant ids, row order
    versions: np.ndarray          # (T,) int64 per-tenant versions
    centers: jax.Array            # (T, C, d) device-resident stack
    index: dict                   # id → row

    @property
    def n_tenants(self) -> int:
        return len(self.ids)

    def row_of(self, tenant) -> int:
        try:
            return self.index[str(tenant)]
        except KeyError:
            raise KeyError(f"unknown tenant {tenant!r} (fleet holds "
                           f"{len(self.ids)} tenants)") from None


def tenant_snapshot(ts: TenantSet) -> TenantSnapshot:
    """Publishable snapshot of a fitted `TenantSet` (centers land on
    device once, here — swaps and dispatches only pass the reference)."""
    return TenantSnapshot(ts.ids, np.asarray(ts.versions, np.int64),
                          jnp.asarray(ts.centers, jnp.float32),
                          {t: i for i, t in enumerate(ts.ids)})


class TenantScorer:
    """A read replica over a hot-swappable `TenantSnapshot`.

    The jitted program takes ``(x (B, d), tidx (B,), centers
    (T, C, d))`` and scores row b against ``centers[tidx[b]]`` — every
    tenant in the batch, one launch.  Centers ride as an ARGUMENT, so
    swapping a same-shape fleet reuses the compiled program; ``traces``
    counts (re)compiles for the regression tests, exactly the `Scorer`
    idiom."""

    def __init__(self, tenants: Union[TenantSet, TenantSnapshot], *,
                 m: float = 2.0, soft: bool = False, replica: str = "t0"):
        self.replica = str(replica)
        self.m = float(m)
        self.soft = bool(soft)
        self._traces = 0

        def _score(x, tidx, centers):
            self._traces += 1           # trace-time compile counter
            v = centers[tidx]                             # (B, C, d)
            d2 = jnp.sum((x[:, None, :] - v) ** 2, axis=-1)   # (B, C)
            return (_u_from_d2(d2, self.m) if self.soft
                    else jnp.argmin(d2, axis=-1))

        self._fn = jax.jit(_score)
        self._snap: Optional[TenantSnapshot] = None
        self.swap(tenants)

    def swap(self, tenants) -> None:
        """Publish a new fleet: ONE atomic attribute store of an
        immutable snapshot.  In-flight dispatches finish against the
        snapshot they already read."""
        self._snap = (tenants if isinstance(tenants, TenantSnapshot)
                      else tenant_snapshot(tenants))

    def read(self) -> TenantSnapshot:
        return self._snap

    @property
    def dim(self) -> int:
        return int(self._snap.centers.shape[2])

    @property
    def traces(self) -> int:
        return self._traces

    def score(self, x, tidx, snap: Optional[TenantSnapshot] = None):
        """Raw gather-scored device call (no padding — the service owns
        batch shaping)."""
        snap = snap if snap is not None else self._snap
        return self._fn(jnp.asarray(x, jnp.float32),
                        jnp.asarray(tidx, jnp.int32), snap.centers)

    def assign(self, tenant, x):
        """Single-shot convenience: ``(assignments, version)`` for one
        tenant against exactly one snapshot."""
        snap = self._snap
        row = snap.row_of(tenant)
        x = np.atleast_2d(np.asarray(x, np.float32))
        with obs.span("tenant.assign", labels={"tenants": "1"},
                      rows=int(x.shape[0])):
            out = np.asarray(self.score(
                x, np.full((x.shape[0],), row, np.int32), snap))
        return out, int(snap.versions[row])

    def __repr__(self):
        return (f"<TenantScorer {self.replica} T={self._snap.n_tenants} "
                f"soft={self.soft}>")


class TenantScoringService(ScoringService):
    """The coalescing front-end with tenant routing.

    ``submit(tenant, x)`` / ``score(tenant, x)`` — requests across
    tenants land on ONE queue and coalesce into ONE gather-scored
    launch per batch bucket; each response reports its own tenant's
    snapshot version (never torn).  The tenant id doubles as the
    fairness group: with ``cfg.max_group_rows`` set, `_take` caps any
    one tenant's rows per dispatch so FIFO coalescing cannot let a
    firehose tenant starve a quiet one."""

    def __init__(self, scorers: Union[TenantScorer,
                                      Sequence[TenantScorer]],
                 cfg: ServiceConfig = ServiceConfig()):
        scorers = ([scorers] if isinstance(scorers, TenantScorer)
                   else list(scorers))
        super().__init__(scorers, cfg)

    # -- client side -------------------------------------------------------

    def submit(self, tenant, x):
        """Enqueue one request for ``tenant``; resolves to a
        `ScoreResult` whose ``version`` is that tenant's snapshot
        version.  Unknown tenants fail fast here (against the current
        snapshot — a concurrent swap that REMOVES the tenant before
        dispatch fails the future instead)."""
        self.scorers[0].read().row_of(tenant)     # fail-fast validation
        return super().submit(x, group=str(tenant))

    def score(self, tenant, x, timeout: Optional[float] = None
              ) -> ScoreResult:
        return self.submit(tenant, x).result(timeout)

    def swap(self, tenants) -> None:
        """Hot-swap EVERY replica to a new fleet (TenantSet or ready
        TenantSnapshot) — one snapshot build, N atomic stores."""
        snap = (tenants if isinstance(tenants, TenantSnapshot)
                else tenant_snapshot(tenants))
        for s in self.scorers:
            s.swap(snap)

    # -- packing -----------------------------------------------------------

    def _pack(self, scorer, reqs) -> _Batch:
        """Pack a cross-tenant batch: rows and their tenant rows against
        ONE fleet read, so every bucket slice scores against this
        version; each request answers with its own tenant's version."""
        snap = scorer.read()
        rows = [snap.row_of(r.group) for r in reqs]
        x = (reqs[0].x if len(reqs) == 1
             else np.concatenate([r.x for r in reqs]))
        tidx = np.concatenate([np.full((r.n,), row, np.int32)
                               for r, row in zip(reqs, rows)])
        pieces = []
        for s, n, b in self._slices(int(x.shape[0])):
            tp = np.zeros((b,), np.int32)   # phantom rows score against
            tp[:n] = tidx[s:s + n]          # row 0 and are sliced off
            pieces.append((n, (pad_rows(x[s:s + n], b), tp)))
        return _Batch(reqs, snap, pieces,
                      [int(snap.versions[row]) for row in rows])
