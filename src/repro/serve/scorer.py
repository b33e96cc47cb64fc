"""Hot-swappable scorer replicas — the fan-out tier of the serving
plane.

The two-tier serving shape (one streaming learner, N read-only
scorers — the local-model/global-model split of the related
hierarchical work) needs the read side to follow the learner's centers
WITHOUT ever blocking or tearing an in-flight request:

  * `CenterSnapshot` — one immutable, self-describing published model:
    ``(version, centers, weights)``.  The center count is free to grow
    and shrink between versions (stream birth/death); nothing here
    assumes a fixed C.
  * `Scorer` — a read replica.  ``swap(snapshot)`` is one atomic
    attribute store of an immutable record; every scoring call reads
    that reference exactly once, so a response is always produced
    against exactly one snapshot version (no torn reads) and a swap
    never waits for in-flight work.  The jitted program takes the
    centers as an ARGUMENT (not a closure constant), so swapping
    same-shape centers re-uses the compiled program — a replica
    recompiles only when a bucket or the center count changes.
  * `SnapshotPublisher` — the learner→replicas bus:
    ``model.add_snapshot_listener(publisher.publish)`` pushes every
    ingest's snapshot to all attached scorers, and (optionally)
    persists it through an `ft.CheckpointManager` so replicas in other
    processes boot from the self-describing manifest
    (`snapshot_from_checkpoint` — grown/shrunk center counts round-trip
    because the manifest records shapes, not a template).
"""
from __future__ import annotations

import threading
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.engine import resolve_backend


class CenterSnapshot(NamedTuple):
    """One published model version: immutable, self-describing."""
    version: int
    centers: np.ndarray               # (C, d) — C may differ per version
    weights: Optional[np.ndarray] = None   # (C,) decayed masses, if known


class _DeviceSnap(NamedTuple):
    """The scorer-internal form: version + device-resident centers, and
    whether hard labels against them come from the backend's tiled
    nearest-center kernel.  Immutable, so one attribute store publishes
    it atomically."""
    version: int
    centers: jax.Array
    tiled: bool = False


def to_device(x) -> jax.Array:
    """A batch on the device as float32: the host-to-device copy, which
    a float32 device array skips."""
    if isinstance(x, jax.Array) and x.dtype == jnp.float32:
        return x
    return jnp.asarray(x, jnp.float32)


class Scorer:
    """A read-only scoring replica over a hot-swappable snapshot.

    ``replica`` is the obs label id (`span.serve.assign{replica=...}`);
    ``soft`` selects membership degrees over hard argmin labels;
    ``backend`` names the engine sweep backend (None/"auto" = the same
    resolution rule the learner uses).
    """

    def __init__(self, snapshot: CenterSnapshot, *, m: float = 2.0,
                 soft: bool = False, backend=None, replica: str = "r0"):
        self.replica = str(replica)
        self.m = float(m)
        self.soft = bool(soft)
        self._backend = be = resolve_backend(backend)
        self._traces = 0

        def _score(x, v):
            # trace-time side effect: counts XLA (re)compiles — the
            # compile-count regression tests read `scorer.traces`
            self._traces += 1
            if self.soft:
                return be.soft_assign(x, v, self.m)
            with jax.named_scope("serve.nearest"):
                return be.hard_assign(x, v)

        self._fn = jax.jit(_score)
        self._snap: Optional[_DeviceSnap] = None
        self.swap(snapshot)

    # -- snapshot following ----------------------------------------------

    def swap(self, snapshot) -> int:
        """Hot-swap to a new snapshot; returns its version.

        Accepts a `CenterSnapshot` or the raw ``(version, centers,
        weights)`` listener signature, so a lone scorer can be wired
        straight to ``StreamingBigFCM.add_snapshot_listener(s.swap)``.
        The publish is ONE attribute store of an immutable record —
        in-flight requests keep the snapshot they already read; the
        next dispatch sees the new one."""
        if not isinstance(snapshot, CenterSnapshot):
            version, centers = snapshot[0], snapshot[1]
        else:
            version, centers = snapshot.version, snapshot.centers
        centers = jnp.asarray(centers, jnp.float32)
        if centers.ndim != 2:
            raise ValueError(f"centers must be (C, d), got "
                             f"{centers.shape}")
        tiled = (not self.soft
                 and self._backend.assigns_by_kernel(centers.shape[0]))
        self._snap = _DeviceSnap(int(version), centers, tiled)
        return int(version)

    @property
    def version(self) -> int:
        return self._snap.version

    @property
    def dim(self) -> int:
        return int(self._snap.centers.shape[1])

    @property
    def tiled(self) -> bool:
        """Whether hard labels against the current snapshot come from
        the backend's C-tiled nearest-center kernel (the shape rule,
        `repro.kernels.ops.nearest_by_kernel`, read once per swap)."""
        return self._snap.tiled

    @property
    def traces(self) -> int:
        """How many distinct programs this replica compiled (one per
        (bucket rows, center count) shape) — regression guard against
        per-request recompiles."""
        return self._traces

    # -- scoring ----------------------------------------------------------

    def read(self) -> _DeviceSnap:
        """The atomic snapshot read — callers that score a padded batch
        themselves (the service workers) take the reference once and
        use its ``centers``/``version`` for the whole batch."""
        return self._snap

    def score(self, x, snap: Optional[_DeviceSnap] = None) -> jax.Array:
        """Score ``x`` against ``snap`` (default: the current
        snapshot); returns without waiting for the device.  No
        padding/instrumentation — the service owns batch shaping and
        the upload (a float32 device ``x`` is used as it is); this is
        the raw device call."""
        snap = snap if snap is not None else self._snap
        return self._fn(to_device(x), snap.centers)

    def assign(self, x):
        """Convenience single-shot scoring: ``(assignments, version)``
        against exactly one snapshot."""
        snap = self._snap
        n = int(np.shape(x)[0])
        with obs.span("serve.assign", labels={"replica": self.replica},
                      rows=n):
            out = np.asarray(self.score(x, snap))
        obs.counter("serve.records", replica=self.replica).add(n)
        return out, snap.version

    def __repr__(self):
        return (f"<Scorer {self.replica} v{self.version} "
                f"C={int(self._snap.centers.shape[0])} soft={self.soft}>")


class SnapshotPublisher:
    """Learner → replicas snapshot bus.

    ``publish(version, centers, weights=None)`` matches the
    `StreamingBigFCM.add_snapshot_listener` signature (also accepts a
    ready `CenterSnapshot` as its single argument); each publish
    hot-swaps every attached scorer and, when a ``ckpt``
    (`ft.CheckpointManager`) is given, persists the snapshot so
    replicas in other processes boot from the manifest."""

    def __init__(self, scorers: Sequence[Scorer] = (), *, ckpt=None):
        self._lock = threading.Lock()
        self._scorers = list(scorers)
        self._ckpt = ckpt
        self._latest: Optional[CenterSnapshot] = None

    def attach(self, scorer: Scorer) -> None:
        """Add a replica; it is swapped to the latest snapshot at once
        (a scorer booted from a stale checkpoint catches up here)."""
        with self._lock:
            self._scorers.append(scorer)
            latest = self._latest
        if latest is not None:
            scorer.swap(latest)

    def publish(self, version, centers=None, weights=None) -> CenterSnapshot:
        if isinstance(version, CenterSnapshot):
            snap = version
        else:
            snap = CenterSnapshot(int(version), np.asarray(centers),
                                  None if weights is None
                                  else np.asarray(weights))
        with self._lock:
            self._latest = snap
            scorers = list(self._scorers)
        for s in scorers:
            s.swap(snap)
        if self._ckpt is not None:
            tree = {"centers": snap.centers}
            if snap.weights is not None:
                tree["weights"] = snap.weights
            self._ckpt.save(snap.version, tree)
        obs.counter("serve.snapshots").add(1)
        obs.event("serve.snapshot", version=snap.version,
                  n_centers=int(snap.centers.shape[0]),
                  replicas=len(scorers))
        return snap

    def latest(self) -> Optional[CenterSnapshot]:
        with self._lock:
            return self._latest


def snapshot_from_checkpoint(ckpt, step: Optional[int] = None
                             ) -> CenterSnapshot:
    """Boot a replica snapshot from a persisted checkpoint: the
    manifest self-describes shapes, so a snapshot whose center count
    grew or shrank since the replica was written restores as-is
    (`CheckpointManager.restore_arrays` — no template pytree).  Works
    against both `SnapshotPublisher(ckpt=...)` snapshots and a full
    `StreamingBigFCM.save` state (the ``centers``/``weights`` leaves
    are read; the rest is ignored)."""
    step = step if step is not None else ckpt.latest_step()
    if step is None:
        raise FileNotFoundError(f"no snapshots in {ckpt.dir}")
    arrs = ckpt.restore_arrays(step)
    if "centers" not in arrs:
        raise KeyError(f"checkpoint step {step} has no 'centers' leaf "
                       f"(leaves: {sorted(arrs)})")
    return CenterSnapshot(int(step), np.asarray(arrs["centers"]),
                          np.asarray(arrs["weights"])
                          if "weights" in arrs else None)
