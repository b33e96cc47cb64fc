"""Batched online scoring service — the throughput front-end over the
pure scoring functions.

`repro.serve` used to be a library (`make_assigner`, `assign_stream`,
`assign_store`); this module is the SERVICE around it, built for the
paper's end state — assignments coming back fast under many-client
load:

  * **Request coalescing.**  Concurrent, arbitrarily-sized requests
    land on one bounded FIFO queue; worker threads drain it greedily,
    packing adjacent requests into one device batch of
    ``max_batch_rows`` so the device amortizes dispatch overhead
    across clients instead of paying it per request.  The request that
    does not fit the room left is split: its first rows fill the
    batch, and its other rows open the same worker's next batch, so
    batches are full whenever that many rows are queued, and each
    response still comes from one replica and one version (a swap
    between the parts has the second batch score the whole request
    again).
  * **Shape-bucketed fixed-shape batches.**  A coalesced batch is
    padded up to the smallest bucket of a geometric ladder
    (`repro.data.plane.shape_buckets` — the same phantom-row padding
    idiom the data plane's `batched` uses), so XLA compiles one program
    per bucket, never one per request size.  Phantom rows are sliced
    off before responses resolve; results are bit-for-bit equal to
    per-request scoring.
  * **Overload policy.**  The queue is bounded in ROWS
    (``queue_rows``).  ``policy="shed"`` rejects immediately with a
    typed `Rejected` when the queue is full — p99 stays bounded because
    no request waits behind unbounded depth.  ``policy="queue"`` blocks
    the submitter until room frees or ``deadline_s`` expires
    (`DeadlineExceeded`).  Either way queue depth is capped and
    admission keeps a progress guarantee: an oversized request is
    admitted whenever the queue is empty.
  * **Fail-loud.**  A scoring error resolves the futures of the
    batches the worker holds with the exception, fails every queued
    request, and closes the service — the regression-tested
    `ShardedLoader` idiom (propagate through the queue, never hang a
    waiting client).  An error launching the next batch still answers
    the batch in flight; an error fetching the batch in flight fails it
    and the next batch too.
  * **Replicas.**  One worker thread per `Scorer` replica keeps each
    device context busy while the queue drains; replicas hot-swap
    snapshots mid-traffic (`swap`, or wire
    ``StreamingBigFCM.add_snapshot_listener(service.swap)``) without
    dropping or blocking in-flight requests — each dispatched batch
    reads its replica's snapshot exactly once, so every response is
    scored against exactly one version.

Observability: the spans of the scoring path, each also a profiler
event (`repro.obs.trace`), nested as they run.  A worker keeps one
batch in flight: it launches batch k+1 before it waits for batch k's
answers, so one turn of its loop is::

    serve.admit              submit, policy "queue": waiting for room
    serve.take               worker: taking batch k+1 (waits only when
                             no batch is in flight and no split
                             request's rest is carried)
    serve.pack               worker: batch k+1's concatenation, padding
                             to the bucket (twice where a swap came
                             between a split request's parts)
    serve.assign{replica=}   worker, per turn (the SLO series)
        serve.upload         host-to-device copy of batch k+1
        serve.launch         the jitted call on batch k+1, not awaited;
                             its copy back starts when the answers exist
        serve.fetch          waiting for batch k's answers on the host
    serve.resolve            worker: batch k's responses, futures and
                             the clients' done-callbacks

A turn with nothing new to take has no ``serve.pack``, and its
``serve.assign`` holds only the fetch of the batch in flight; the turn
that launches the first batch has no ``serve.fetch`` and no
``serve.resolve``.  ``serve.take``, ``serve.pack``, ``serve.assign``
and ``serve.resolve`` tile the worker loop.  Metrics: the
``serve.queue_rows`` gauge; ``serve.shed``/``serve.shed_rows``/
``serve.deadline_expired`` counters; per-replica ``serve.records``/
``serve.batches``/``serve.served`` counters, ``serve.overlapped``
(batches launched while another was in flight), ``serve.split``
(requests split across two batches) and ``serve.tiled``
(batches whose hard labels came from the backend's C-tiled
nearest-center kernel; the device scope ``serve.nearest`` holds the
hard-label assignment on either path); the
``serve.request`` end-to-end (submit → response) latency histogram.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from collections import deque
from concurrent.futures import Future
from typing import NamedTuple, Optional, Sequence, Union

import jax
import numpy as np

from repro import obs
from repro.data.plane import bucket_for, pad_rows, shape_buckets

from .scorer import CenterSnapshot, Scorer


class Rejected(RuntimeError):
    """Typed shed rejection: the bounded queue was full and
    ``policy="shed"`` chose latency over this request.  Carries the
    queue state so clients can back off proportionally."""

    def __init__(self, msg: str, *, queued_rows: int, limit_rows: int):
        super().__init__(msg)
        self.queued_rows = int(queued_rows)
        self.limit_rows = int(limit_rows)


class DeadlineExceeded(RuntimeError):
    """``policy="queue"``: the submitter waited ``deadline_s`` for
    queue room that never freed."""


class ServiceClosed(RuntimeError):
    """Submit after `close()` (or a request drained by a non-draining
    close)."""


class ScoreResult(NamedTuple):
    """One response: assignments for the request's rows (hard labels
    ``(n,)`` or soft memberships ``(n, C)``), the snapshot ``version``
    they were scored against (exactly one — never torn across a
    hot-swap), and the ``replica`` that served them."""
    assignments: np.ndarray
    version: int
    replica: str


@dataclasses.dataclass(frozen=True)
class ServiceConfig:
    """Knobs of the scoring front-end.

    ``max_batch_rows`` caps one device dispatch (and tops the bucket
    ladder); ``bucket_base``/``bucket_factor`` shape the ladder;
    ``queue_rows`` bounds the queue in rows; ``policy`` picks the
    overload response (``"queue"`` waits up to ``deadline_s``,
    ``"shed"`` rejects immediately); ``coalesce=False`` is the
    one-request-one-dispatch ablation (every request scored at its
    natural shape — the benchmark baseline, not a production mode).

    ``max_group_rows`` is the fairness cap: requests may carry a group
    id (the tenant plane tags each request with its tenant), and with
    the cap set one group contributes at most that many rows per
    dispatch — the coalescer takes eligible requests past an ineligible
    run in FIFO order, so a firehose group cannot monopolize every
    batch while a quiet group's lone request ages at position 300.
    ``None`` (default) keeps strict FIFO coalescing; the queue head is
    always admitted (progress guarantee)."""
    max_batch_rows: int = 4096
    bucket_base: int = 64
    bucket_factor: int = 2
    queue_rows: int = 65536
    policy: str = "queue"            # "queue" | "shed"
    deadline_s: float = 5.0
    coalesce: bool = True
    max_group_rows: Optional[int] = None

    def __post_init__(self):
        if self.policy not in ("queue", "shed"):
            raise ValueError(f"policy must be 'queue' or 'shed', got "
                             f"{self.policy!r}")
        if self.max_batch_rows <= 0 or self.queue_rows <= 0:
            raise ValueError("max_batch_rows and queue_rows must be "
                             "positive")
        if self.deadline_s <= 0:
            raise ValueError("deadline_s must be positive")
        if self.max_group_rows is not None and self.max_group_rows <= 0:
            raise ValueError("max_group_rows must be positive (or None "
                             "to disable the fairness cap)")


class _Request(NamedTuple):
    x: np.ndarray
    n: int
    future: Future
    t_submit: float
    group: Optional[str] = None   # fairness group (tenant id)
    # a request split across two batches: its first part leaves its
    # answers in ``stash``, the part that answers reads them as ``head``
    stash: Optional[list] = None
    head: Optional[list] = None


class _Carry(NamedTuple):
    """The other rows of a request split across two batches, held by the
    worker that split it: ``part`` opens that worker's next batch,
    ``whole`` is the request, and ``snap`` the snapshot its first part
    was packed against."""
    part: _Request
    whole: _Request
    snap: object = None


def _split(r: _Request, k: int):
    """``r`` split after its first ``k`` rows: the first part, which
    answers nobody, and the carry of the rest."""
    answers = []
    return (r._replace(x=r.x[:k], n=k, stash=answers),
            _Carry(r._replace(x=r.x[k:], n=r.n - k, head=answers), r))


class _Batch(NamedTuple):
    """One packed batch: its requests, the snapshot it scores against
    (read once, when it is packed), its device batches as ``(rows,
    host arguments of the scorer's call)``, and each request's
    version."""
    reqs: list
    snap: object
    pieces: list
    versions: list


class _Metrics(NamedTuple):
    """The hot path's metric handles, bound once (`obs.Handles`)."""
    queue_rows: obs.Gauge
    request: obs.Histogram
    replicas: dict      # replica id -> (records, batches, served,
    #                     overlapped, tiled, split)


class ScoringService:
    """The coalescing front-end over N hot-swappable `Scorer` replicas.

    ``submit(x)`` returns a `Future` resolving to a `ScoreResult`;
    ``score(x)`` is the synchronous wrapper.  One worker thread per
    replica drains the shared queue.  Use as a context manager, or
    `close()` explicitly."""

    def __init__(self, scorers: Union[Scorer, Sequence[Scorer]],
                 cfg: ServiceConfig = ServiceConfig()):
        scorers = ([scorers] if isinstance(scorers, Scorer)
                   else list(scorers))
        if not scorers:
            raise ValueError("ScoringService needs at least one Scorer")
        dims = {s.dim for s in scorers}
        if len(dims) != 1:
            raise ValueError(f"replicas disagree on feature dim: {dims}")
        names = [s.replica for s in scorers]
        if len(set(names)) != len(names):
            raise ValueError(f"replica ids must be unique, got {names}")
        self.scorers = scorers
        self.cfg = cfg
        self._dim = dims.pop()
        self._buckets = shape_buckets(cfg.max_batch_rows,
                                      base=cfg.bucket_base,
                                      factor=cfg.bucket_factor)
        self._cond = threading.Condition()
        self._queue: deque = deque()
        self._queued_rows = 0
        self._closed = False
        self._failure: Optional[BaseException] = None
        self._obs = obs.Handles(self._bind_metrics)
        self._threads = [
            threading.Thread(target=self._worker, args=(s,),
                             name=f"serve-{s.replica}", daemon=True)
            for s in scorers]
        for t in self._threads:
            t.start()

    # -- client side -------------------------------------------------------

    def submit(self, x, *, group: Optional[str] = None) -> Future:
        """Enqueue one assignment request; resolves to a `ScoreResult`.

        Shape/dim errors raise here (fail fast, nothing enqueued);
        overload raises `Rejected` (shed) or `DeadlineExceeded`
        (queue); scoring failures resolve the future with the
        exception.  ``group`` tags the request for the
        ``max_group_rows`` fairness cap (the tenant service passes the
        tenant id)."""
        x = np.asarray(x, np.float32)
        if x.ndim == 1:
            x = x[None, :]
        if x.ndim != 2 or x.shape[0] == 0:
            raise ValueError(f"request must be (n>=1, d), got {x.shape}")
        if x.shape[1] != self._dim:
            raise ValueError(f"request dim {x.shape[1]} != model dim "
                             f"{self._dim}")
        n = int(x.shape[0])
        req = _Request(x, n, Future(), time.perf_counter(), group)
        with self._cond:
            self._check_open()
            if not self._admissible(n):
                if self.cfg.policy == "shed":
                    obs.counter("serve.shed").add(1)
                    obs.counter("serve.shed_rows").add(n)
                    raise Rejected(
                        f"queue full ({self._queued_rows} rows >= "
                        f"{self.cfg.queue_rows}); request of {n} rows "
                        f"shed", queued_rows=self._queued_rows,
                        limit_rows=self.cfg.queue_rows)
                deadline = time.monotonic() + self.cfg.deadline_s
                with obs.span("serve.admit"):
                    while not self._admissible(n):
                        remaining = deadline - time.monotonic()
                        if remaining <= 0:
                            obs.counter("serve.deadline_expired").add(1)
                            raise DeadlineExceeded(
                                f"no queue room for {n} rows within "
                                f"{self.cfg.deadline_s}s")
                        self._cond.wait(remaining)
                        self._check_open()
            self._queue.append(req)
            self._queued_rows += n
            self._gauge()
            self._cond.notify_all()
        return req.future

    def score(self, x, timeout: Optional[float] = None) -> ScoreResult:
        """Synchronous `submit`: block for this request's result."""
        return self.submit(x).result(timeout)

    def swap(self, version, centers=None, weights=None) -> None:
        """Hot-swap EVERY replica to a new snapshot — matches the
        ``(version, centers, weights)`` listener signature, so
        ``model.add_snapshot_listener(service.swap)`` follows a live
        learner; also accepts a ready `CenterSnapshot`.  Never blocks
        on in-flight requests: dispatched batches finish against the
        snapshot they already read; the next batch per replica sees
        the new version."""
        if isinstance(version, CenterSnapshot):
            snap = version
        else:
            snap = CenterSnapshot(int(version), np.asarray(centers),
                                  None if weights is None
                                  else np.asarray(weights))
        for s in self.scorers:
            s.swap(snap)

    @property
    def buckets(self):
        """The row-count bucket ladder requests are padded onto."""
        return self._buckets

    def compile_counts(self) -> dict:
        """Per-replica XLA trace counts — the compile-once-per-bucket
        regression guard reads this."""
        return {s.replica: s.traces for s in self.scorers}

    def close(self, *, drain: bool = True) -> None:
        """Stop accepting requests.  ``drain=True`` (default) serves
        everything already queued before workers exit; ``drain=False``
        fails queued requests with `ServiceClosed`."""
        with self._cond:
            if self._closed:
                self._cond.notify_all()
            self._closed = True
            pending = []
            if not drain:
                pending = list(self._queue)
                self._queue.clear()
                self._queued_rows = 0
                self._gauge()
            self._cond.notify_all()
        for r in pending:
            r.future.set_exception(ServiceClosed(
                "service closed before this request was scored"))
        for t in self._threads:
            t.join()

    def __enter__(self) -> "ScoringService":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False

    # -- internals ---------------------------------------------------------

    def _admissible(self, n: int) -> bool:
        # empty-queue admission keeps a progress guarantee for
        # requests bigger than the row bound (split across dispatches
        # by the worker, all against one snapshot)
        return (self._queued_rows == 0
                or self._queued_rows + n <= self.cfg.queue_rows)

    def _check_open(self) -> None:
        if self._failure is not None:
            raise RuntimeError(
                "scoring service failed; see the cause") from self._failure
        if self._closed:
            raise ServiceClosed("scoring service is closed")

    def _bind_metrics(self) -> _Metrics:
        return _Metrics(
            obs.gauge("serve.queue_rows"), obs.histogram("serve.request"),
            {s.replica: tuple(obs.counter(name, replica=s.replica)
                              for name in ("serve.records", "serve.batches",
                                           "serve.served",
                                           "serve.overlapped",
                                           "serve.tiled", "serve.split"))
             for s in self.scorers})

    def _gauge(self) -> None:
        self._obs.get().queue_rows.set(self._queued_rows)

    def _take(self, wait: bool, carry: Optional[_Carry] = None):
        """Pop requests for one dispatch, filled up to ``max_batch_rows``.
        Returns the requests and, where the batch split a request, the
        `_Carry` of its other rows; no requests means nothing to take:
        the queue is empty and ``wait`` is off, or the worker should
        exit.  A held ``carry`` is always taken, never waited for.

        The batch opens with ``carry``'s part, else with the queue's
        head, taken whole (an oversized one is sliced by `_slices`),
        and takes the next requests in FIFO order while they fit.  The
        first that does not fit the room left stops the batch: its first
        rows fill the room, and its other rows, carried, open this
        worker's next batch.  So every batch is full while a batch's
        worth of rows is queued, and nothing is split with fewer.  With
        ``max_group_rows`` set, a request whose group is at its cap
        (counting a split's first part) is skipped, keeping its queue
        position, and the scan goes on; the head is always admitted, so
        every request still drains in bounded dispatches.  Without
        coalescing a batch is one request, never split."""
        with self._cond:
            while (wait and carry is None and not self._queue
                   and self._failure is None and not self._closed):
                self._cond.wait()
            if carry is not None:
                head, freed = carry.part, 0
            elif self._failure is not None or not self._queue:
                return [], None
            else:
                head = self._queue.popleft()
                freed = head.n
            reqs, split = [head], None
            room = self.cfg.max_batch_rows - head.n
            cap = self.cfg.max_group_rows
            group_rows = {head.group: head.n}
            skipped = []
            while self.cfg.coalesce and room > 0 and self._queue:
                r = self._queue.popleft()
                taken = group_rows.get(r.group, 0)
                if cap is not None and taken + min(r.n, room) > cap:
                    skipped.append(r)
                    continue
                freed += r.n
                if r.n > room:                # r stops the batch
                    r, split = _split(r, room)
                reqs.append(r)
                room -= r.n
                group_rows[r.group] = taken + r.n
            self._queue.extendleft(reversed(skipped))  # FIFO order kept
            self._queued_rows -= freed
            self._gauge()
            self._cond.notify_all()      # room freed: wake submitters
            return reqs, split

    def _worker(self, scorer: Scorer) -> None:
        """One replica's loop, with one batch in flight: take and launch
        batch k+1, then wait for batch k's answers and deliver them, so
        the device's round trip for k overlaps the host's work on k+1.
        With a batch in flight and the queue empty the take does not
        wait: the worker answers the batch in flight at once, and a lone
        request waits for no later arrival.

        A request that batch k+1 split opens the worker's next batch
        with its other rows; where the snapshot changed in between, that
        batch scores the whole request again, so it answers with one
        version.

        A failure fails every request the worker holds that is not
        answered yet (`_fail` skips answered futures; a split request is
        held while either part is): an error packing or launching k+1
        lets k be fetched and answered first."""
        *_, overlapped, _, split_count = self._obs.get().replicas[
            scorer.replica]
        labels = {"replica": scorer.replica}
        held = None        # batch k: (batch, device results), not fetched
        carry = None       # the rest of a request batch k+1 split
        while True:
            with obs.span("serve.take"):
                reqs, split = self._take(held is None, carry)
            if not reqs and held is None:
                return
            nxt = error = None
            if reqs:
                try:
                    with obs.span("serve.pack"):
                        batch = self._pack(scorer, reqs)
                        if carry is not None and batch.snap is not carry.snap:
                            reqs[0] = carry.whole     # swapped since
                            batch = self._pack(scorer, reqs)
                except BaseException as e:    # noqa: BLE001 — fail-loud
                    error = e
                else:
                    if split is not None:
                        split_count.add(1)
                        split = split._replace(snap=batch.snap)
                carry = split
            with obs.span("serve.assign", labels=labels):
                if reqs and error is None:
                    try:
                        nxt = batch, self._launch(scorer, batch)
                    except BaseException as e:    # noqa: BLE001
                        error = e
                    else:
                        if held is not None:
                            overlapped.add(1)
                if held is not None:
                    try:
                        with obs.span("serve.fetch"):
                            outs = [np.asarray(res)[:n]
                                    for n, res in held[1]]
                    except BaseException as e:    # noqa: BLE001
                        error = e
                        outs = None
            if held is not None:
                if outs is not None:
                    try:
                        self._resolve(scorer, held[0], outs)
                    except BaseException as e:    # noqa: BLE001
                        error = e
                reqs = held[0].reqs + reqs
            if error is not None:
                self._fail(error, reqs)
                return
            held = nxt

    def _pack(self, scorer: Scorer, reqs) -> _Batch:
        """Pack ``reqs`` into device batches against ONE atomic snapshot
        read: every bucket slice of an oversized request scores against
        this version.  Subclasses that score other arguments override
        this alone."""
        snap = scorer.read()
        x = (reqs[0].x if len(reqs) == 1
             else np.concatenate([r.x for r in reqs]))
        pieces = [(n, (pad_rows(x[s:s + n], b),))
                  for s, n, b in self._slices(int(x.shape[0]))]
        return _Batch(reqs, snap, pieces, [snap.version] * len(reqs))

    def _slices(self, total: int) -> list:
        """``(start, rows, bucket)`` of each device batch that ``total``
        packed rows fill: slices of at most ``max_batch_rows``, each
        padded to its bucket; without coalescing, the one request at
        its natural shape (the ablation: no slicing, no pad)."""
        if not self.cfg.coalesce:
            return [(0, total, total)]
        maxb = self.cfg.max_batch_rows
        out = []
        for start in range(0, total, maxb):
            n = min(maxb, total - start)
            out.append((start, n, bucket_for(n, self._buckets)))
        return out

    def _launch(self, scorer, batch: _Batch) -> list:
        """Upload and launch every device batch of ``batch``; returns
        ``(rows, result)`` pairs whose copy back to the host has started
        and is not awaited."""
        results = []
        for n, args in batch.pieces:
            with obs.span("serve.upload"):
                args = jax.device_put(args)
            with obs.span("serve.launch"):
                res = scorer.score(*args, batch.snap)
                res.copy_to_host_async()
            results.append((n, res))
        return results

    def _resolve(self, scorer, batch: _Batch, outs) -> None:
        """Answer ``batch``'s requests from its fetched pieces ``outs``,
        each with its version; the clients' done-callbacks run here.  A
        split's first part answers nobody: its answers wait for the
        part that answers the request, which opens a later batch."""
        with obs.span("serve.resolve"):
            m = self._obs.get()
            records, batches, served, _, tiled, _ = m.replicas[
                scorer.replica]
            out = outs[0] if len(outs) == 1 else np.concatenate(outs)
            records.add(out.shape[0])
            batches.add(1)
            # only `Scorer` has a tiled path; other scorers (tenants,
            # stand-ins) have no such attribute
            if getattr(scorer, "tiled", False):
                tiled.add(1)
            off = 0
            done = time.perf_counter()
            for r, version in zip(batch.reqs, batch.versions):
                a = out[off:off + r.n]
                off += r.n
                if r.stash is not None:
                    r.stash.append(a)
                    continue
                if r.head:
                    a = np.concatenate(r.head + [a])
                m.request.observe(done - r.t_submit)
                served.add(1)
                r.future.set_result(ScoreResult(a, version, scorer.replica))

    def _fail(self, exc: BaseException, reqs) -> None:
        """The ShardedLoader contract, service-shaped: the error
        reaches every waiting client through its future (no hangs),
        the queue drains failed, and later submits raise with the
        original cause."""
        for r in reqs:
            if not r.future.done():
                r.future.set_exception(exc)
        with self._cond:
            self._failure = exc
            pending = list(self._queue)
            self._queue.clear()
            self._queued_rows = 0
            self._gauge()
            self._cond.notify_all()
        for r in pending:
            if not r.future.done():
                r.future.set_exception(exc)
        obs.event("serve.failed", error=repr(exc))
