"""Traffic of kind ``serve``: open-loop scoring through
`ScoringService.submit`.

Set-up makes a pool of the configuration's records from the seed on the
device and copies it to the host, where clients hold their records.
The published centers are ``clusters`` records of the pool drawn by the
seed.  One `Scorer` on the configuration's backend serves them behind a
`ScoringService` with its default `ServiceConfig`; it answers nearest
centers, or, where the mix sets ``memberships``, each row's fuzzy
membership in every center.  Warm-up scores one
request at every bucket of the service's ladder, so that every program
the window needs is compiled, then sends a short burst at the cell's
rate.

The window sends requests on a fixed schedule from one thread, whatever
the service does (open loop).  Every seed gets the same multiset of
request sizes and of gaps between arrivals, in its own order: sizes are
the quantiles of a log-uniform law over ``[rows_min, rows_max]``, gaps
those of an exponential law at ``rate_per_s`` (Poisson arrivals),
scaled to fill the window.  Where the mix has ``burst_on_s`` and
``burst_off_s``, each period of ``on + off`` seconds packs its
arrivals into its first ``on`` seconds: the same mean rate, in bursts.  Each request is timed from when it was due,
not from when it was sent.  Requests that fail or never complete count
as missing every limit.

After the window, every row of every response (or of a sample of
``check_requests`` responses drawn from the seed) is compared with the
float64 nearest center (`reference.assignment_gaps`), or with the
float64 memberships (`reference.membership_gaps`).
"""
from __future__ import annotations

import threading
import time
from functools import partial

import numpy as np

from bench.harness import reference
from bench.harness.data import make_data

REPLICA = "chip0"
# a request's status: not answered (yet), answered, refused at the door
# (`Rejected`, `DeadlineExceeded`), or answered with an error
PENDING, ANSWERED, REFUSED, ERROR = 0, 1, 2, 3
WAIT_AFTER_S = 60.0              # how long answers may come after close
WARMUP_REQUESTS = 200


def nearest_rank(values: np.ndarray, q: float) -> float:
    """The ``q``-quantile by nearest rank (``inf`` entries allowed)."""
    v = np.sort(np.asarray(values, np.float64))
    return float(v[max(int(np.ceil(q * len(v))) - 1, 0)])


class Traffic:
    def __init__(self, cfg: dict, traffic: dict, seed: int, clock,
                 rows: int | None = None):
        from repro.serve import (CenterSnapshot, Scorer, ScoringService,
                                 ServiceConfig)

        self.cfg, self.traffic = cfg, traffic
        self.soft = bool(traffic.get("memberships", False))
        self.rng = np.random.default_rng(int(seed) % (1 << 64))
        pool_rows = int(rows or traffic["pool_rows"])
        self.pool = np.asarray(make_data(cfg, seed, pool_rows))
        idx = self.rng.choice(pool_rows, int(cfg["clusters"]),
                              replace=False)
        self.centers = self.pool[np.sort(idx)]
        scorer = Scorer(CenterSnapshot(1, self.centers), m=float(cfg["m"]),
                        soft=self.soft, backend=cfg["backend"],
                        replica=REPLICA)
        self.svc = ScoringService([scorer], ServiceConfig())
        for b in self.svc.buckets:
            self.svc.score(self.pool[:b])
        self._send(self._schedule(WARMUP_REQUESTS / traffic["rate_per_s"]))

    # -- the schedule --------------------------------------------------------

    def _schedule(self, seconds: float):
        """``(due, size, offset)`` arrays for ``seconds`` of arrivals."""
        t = self.traffic
        n = max(int(round(t["rate_per_s"] * seconds)), 1)
        u = (np.arange(n) + 0.5) / n
        gaps = -np.log1p(-u)
        gaps *= seconds / gaps.sum()
        lo, hi = np.log(t["rows_min"]), np.log(t["rows_max"] + 1)
        sizes = np.minimum(np.floor(np.exp(lo + u * (hi - lo))),
                           t["rows_max"]).astype(np.int64)
        gaps = self.rng.permutation(gaps)
        sizes = self.rng.permutation(sizes)
        due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
        if "burst_on_s" in t:
            on, period = t["burst_on_s"], t["burst_on_s"] + t["burst_off_s"]
            start = np.floor(due / period) * period
            due = start + (due - start) * (on / period)
        offsets = self.rng.integers(0, len(self.pool) - sizes + 1)
        return due, sizes, offsets

    def _send(self, sched):
        """Send ``sched`` open loop.  Each answer is copied into flat
        arrays as it completes (no future is kept, so the generator
        holds few objects between requests)."""
        from repro.serve.service import DeadlineExceeded, Rejected

        due, sizes, offsets = sched
        n = len(due)
        start = np.concatenate([[0], np.cumsum(sizes)[:-1]])
        shape = ((int(sizes.sum()), len(self.centers)) if self.soft
                 else (int(sizes.sum()),))
        rec = {"due": None, "sent": np.empty(n), "done": np.full(n, np.nan),
               "sizes": sizes, "offsets": offsets, "start": start,
               "status": np.zeros(n, np.int8),      # see `STATUS`
               "assigned": np.empty(shape, np.float32 if self.soft
                                    else np.int32)}
        pending = [0]
        lock = threading.Condition()

        def finished(i, fut):
            t = time.perf_counter()
            if fut.exception() is not None:
                code = ERROR
            else:
                got = np.asarray(fut.result().assignments)
                want = (sizes[i],) + rec["assigned"].shape[1:]
                code = ANSWERED if got.shape == want else ERROR
                if code == ANSWERED:
                    rec["assigned"][start[i]:start[i] + sizes[i]] = got
            with lock:
                rec["done"][i] = t
                rec["status"][i] = code
                pending[0] -= 1
                lock.notify_all()

        t0 = time.perf_counter() + 0.005
        rec["due"] = due_abs = t0 + due
        for i in range(n):
            while True:
                wait = due_abs[i] - time.perf_counter()
                if wait <= 0:
                    break
                time.sleep(wait)
            rec["sent"][i] = time.perf_counter()
            o, s = int(offsets[i]), int(sizes[i])
            with lock:
                pending[0] += 1
            try:
                fut = self.svc.submit(self.pool[o:o + s])
            except (Rejected, DeadlineExceeded):
                with lock:
                    pending[0] -= 1
                rec["status"][i] = REFUSED
                continue
            fut.add_done_callback(partial(finished, i))
        limit = (t0 + due[-1] if n else t0) + WAIT_AFTER_S
        with lock:
            while pending[0] and time.perf_counter() < limit:
                lock.wait(max(limit - time.perf_counter(), 0.0))
        rec["t0"] = t0
        return rec

    # -- the window ----------------------------------------------------------

    def _counters(self):
        from repro import obs

        span = obs.histogram("span.serve.assign")
        return (obs.counter("serve.records", replica=REPLICA).value,
                obs.counter("serve.batches", replica=REPLICA).value,
                span.sum, span.count)

    def window(self, seconds: float, annotate=None) -> dict:
        before = self._counters()
        rec = self._send(self._schedule(seconds))
        after = self._counters()
        rec["seconds"] = float(seconds)
        rec["obs"] = dict(zip(("records", "batches", "assign_s",
                               "assign_n"),
                              (a - b for a, b in zip(after, before))))
        return rec

    def release(self) -> None:
        self.svc.close()

    def _answered(self, rec: dict) -> np.ndarray:
        return rec["status"] == ANSWERED

    def latencies(self, rec: dict) -> np.ndarray:
        """Seconds from due to done; ``inf`` where no answer came."""
        lat = rec["done"] - rec["due"]
        return np.where(self._answered(rec), lat, np.inf)

    def end_to_end(self, rec: dict) -> dict:
        end = rec["t0"] + rec["seconds"]
        in_window = self._answered(rec) & (rec["done"] <= end)
        return {"score_records_per_s":
                float(rec["sizes"][in_window].sum()) / rec["seconds"]}

    def counts(self, rec: dict) -> tuple:
        n = len(rec["status"])
        return n, int(n - self._answered(rec).sum())

    def readings(self, rec: dict) -> dict:
        """``unanswered``: requests sent whose answer never came or was
        an error (a refusal at the door is a failure, not a wrong
        answer); ``assignment_gap`` (nearest centers) or
        ``membership_gap`` (memberships): the widest gap over every row
        of the answers, all of them or ``check_requests`` of them drawn
        from the seed."""
        st = rec["status"]
        unanswered = int(((st == PENDING) | (st == ERROR)).sum())
        answered = np.flatnonzero(st == ANSWERED)
        k = int(self.traffic["check_requests"])
        if len(answered) > k:
            answered = np.sort(self.rng.choice(answered, k, replace=False))
        worst = 0.0
        for i in answered:              # request by request: small blocks
            o, s, a = (int(rec["offsets"][i]), int(rec["sizes"][i]),
                       int(rec["start"][i]))
            worst = max(worst, self._gap(self.pool[o:o + s],
                                         rec["assigned"][a:a + s]))
        name = "membership_gap" if self.soft else "assignment_gap"
        return {"unanswered": unanswered, name: worst}

    def _gap(self, x: np.ndarray, got: np.ndarray) -> float:
        if self.soft:
            return float(reference.membership_gaps(
                x, got, self.centers, float(self.cfg["m"])).max())
        return float(reference.assignment_gaps(x, got, self.centers).max())
