"""Serving plane (PR 8): batched scoring service, hot-swap replicas,
shape-bucket padding, and the overload policies.

Covers the ISSUE-8 satellite checklist: shed bounds queue depth with a
typed rejection; queue policy preserves request→response ordering and
bit-for-bit exactness vs per-request scoring; a hot-swap mid-traffic
never tears a response across snapshot versions; the ragged store tail
scores through one compiled program; per-replica obs labels.
"""
import tempfile
import threading
import time

import numpy as np
import pytest

from repro import obs
from repro.data import ChunkStore, bucket_for, pad_rows, shape_buckets
from repro.ft import CheckpointManager
from repro.serve import (CenterSnapshot, DeadlineExceeded, Rejected,
                         Scorer, ScoringService, ServiceClosed,
                         ServiceConfig, SnapshotPublisher, TenantScorer,
                         TenantScoringService, assign_store,
                         make_assigner, snapshot_from_checkpoint)
from repro.stream import StreamConfig, StreamingBigFCM
from repro.tenant import tenant_set

from conftest import seeded_cases

RNG = np.random.default_rng(0)
D = 6


@pytest.fixture(autouse=True)
def _fresh_obs():
    obs.reset_all()
    yield
    obs.reset_all()


def _centers(c=5, seed=0):
    return (np.random.default_rng(seed).normal(size=(c, D)) * 4.0
            ).astype(np.float32)


def _reqs(k, lo=1, hi=200, seed=1):
    rng = np.random.default_rng(seed)
    return [rng.normal(size=(int(n), D)).astype(np.float32)
            for n in rng.integers(lo, hi, size=k)]


class _Gated:
    """Blocks every score call on an event — backs up the queue so
    overload-policy tests are deterministic; ``entered`` is set once a
    call has reached the gate."""

    def __init__(self, *a, **k):
        self.gate = threading.Event()
        self.entered = threading.Event()
        super().__init__(*a, **k)

    def score(self, *a):
        self.entered.set()
        self.gate.wait(10)
        return super().score(*a)


class GatedScorer(_Gated, Scorer):
    pass


class GatedTenantScorer(_Gated, TenantScorer):
    pass


class PoisonScorer(Scorer):
    def score(self, x, snap=None):
        raise ValueError("poisoned scorer")


# ------------------------------------------------------- bucket helpers --

def test_shape_bucket_ladder():
    assert shape_buckets(4096, base=64) == (64, 128, 256, 512, 1024,
                                            2048, 4096)
    assert shape_buckets(100, base=64) == (64, 100)   # max always in
    assert shape_buckets(32, base=64) == (32,)
    assert bucket_for(1, (64, 128)) == 64
    assert bucket_for(64, (64, 128)) == 64
    assert bucket_for(65, (64, 128)) == 128
    with pytest.raises(ValueError):
        bucket_for(129, (64, 128))


def test_pad_rows_phantom():
    x = RNG.normal(size=(3, D)).astype(np.float32)
    p = pad_rows(x, 8)
    assert p.shape == (8, D)
    assert np.array_equal(p[:3], x) and not p[3:].any()
    assert pad_rows(x, 3) is not x or True     # same-rows passthrough ok
    with pytest.raises(ValueError):
        pad_rows(x, 2)


# ------------------------------------------------- coalescing exactness --

def test_coalesced_equals_per_request_bit_for_bit():
    """The batching acceptance: coalesced, padded, bucketed scoring
    equals the per-request result after unpadding — hard labels
    bit-for-bit; soft membership floats to the ulp (row position
    inside a differently-shaped XLA batch may flip the last bit of a
    float, never a label)."""
    centers = _centers()
    for soft in (False, True):
        svc = ScoringService(
            Scorer(CenterSnapshot(0, centers), soft=soft, backend="jnp"),
            ServiceConfig(max_batch_rows=512, bucket_base=32))
        with svc:
            reqs = _reqs(40)
            futs = [svc.submit(r) for r in reqs]
            ref_fn = make_assigner(centers, soft=soft, backend="jnp")
            for r, f in zip(reqs, futs):
                res = f.result(30)
                ref = np.asarray(ref_fn(r))
                if soft:
                    np.testing.assert_allclose(res.assignments, ref,
                                               rtol=0, atol=1e-6)
                else:
                    assert np.array_equal(res.assignments, ref)
                assert res.version == 0 and res.replica == "r0"


def test_oversized_request_spans_buckets_one_version():
    """A request bigger than max_batch_rows is sliced across several
    fixed-shape dispatches against ONE snapshot read."""
    centers = _centers()
    svc = ScoringService(Scorer(CenterSnapshot(7, centers), backend="jnp"),
                         ServiceConfig(max_batch_rows=256, bucket_base=64))
    with svc:
        big = RNG.normal(size=(1000, D)).astype(np.float32)
        res = svc.score(big, timeout=30)
    assert res.assignments.shape == (1000,)
    assert res.version == 7
    assert np.array_equal(res.assignments,
                          np.asarray(make_assigner(centers,
                                                   backend="jnp")(big)))


def test_queue_policy_preserves_fifo_ordering():
    order = []
    svc = ScoringService(Scorer(CenterSnapshot(0, _centers()),
                                backend="jnp"),
                         ServiceConfig(max_batch_rows=128, policy="queue"))
    with svc:
        futs = []
        for i, r in enumerate(_reqs(30, lo=1, hi=60)):
            f = svc.submit(r)
            f.add_done_callback(lambda _f, i=i: order.append(i))
            futs.append(f)
        for f in futs:
            f.result(30)
    assert order == sorted(order)


# ------------------------------------------------------------- overload --

def test_shed_policy_bounds_queue_and_rejects_typed():
    scorer = GatedScorer(CenterSnapshot(0, _centers()), backend="jnp")
    cfg = ServiceConfig(max_batch_rows=64, queue_rows=256, policy="shed")
    svc = ScoringService(scorer, cfg)
    x = np.zeros((64, D), np.float32)
    admitted = [svc.submit(x)]          # taken by the (gated) worker
    time.sleep(0.1)                     # let the worker pick it up
    shed = 0
    for _ in range(20):
        try:
            admitted.append(svc.submit(x))
        except Rejected as e:
            shed += 1
            assert e.limit_rows == 256
            assert e.queued_rows + 64 > 256
    assert shed > 0                     # overload actually shed
    # the queue never grew past the row bound
    assert obs.gauge("serve.queue_rows").max <= 256
    assert obs.counter("serve.shed").value == shed
    scorer.gate.set()                   # drain: everything admitted serves
    for f in admitted:
        assert f.result(30).assignments.shape == (64,)
    svc.close()
    snap = obs.metrics_snapshot()
    assert snap["counters"]["serve.served{replica=r0}"] == len(admitted)


def test_queue_policy_deadline_is_typed_and_bounded():
    scorer = GatedScorer(CenterSnapshot(0, _centers()), backend="jnp")
    cfg = ServiceConfig(max_batch_rows=64, queue_rows=128,
                        policy="queue", deadline_s=0.2)
    svc = ScoringService(scorer, cfg)
    x = np.zeros((64, D), np.float32)
    f0 = svc.submit(x)                  # worker takes it, blocks on gate
    time.sleep(0.1)
    f1 = svc.submit(x)                  # fills the queue (64+64 > 128-64)
    f2 = svc.submit(x)
    t0 = time.monotonic()
    with pytest.raises(DeadlineExceeded):
        svc.submit(x)
    assert 0.1 < time.monotonic() - t0 < 2.0
    assert obs.counter("serve.deadline_expired").value == 1
    scorer.gate.set()
    for f in (f0, f1, f2):
        f.result(30)
    svc.close()


def test_scoring_failure_propagates_never_hangs():
    """The ShardedLoader contract: a poisoned scorer fails the batch's
    futures, fails everything queued, and later submits raise — no
    client ever blocks forever."""
    scorer = PoisonScorer(CenterSnapshot(0, _centers()), backend="jnp")
    svc = ScoringService(scorer, ServiceConfig(max_batch_rows=64))
    futs = [svc.submit(np.zeros((32, D), np.float32)) for _ in range(4)]
    for f in futs:
        with pytest.raises(ValueError, match="poisoned"):
            f.result(30)
    # the failure latches: submitting into a dead service raises loud
    with pytest.raises(RuntimeError):
        for _ in range(50):
            svc.submit(np.zeros((8, D), np.float32)).result(30)
            time.sleep(0.01)


def test_close_rejects_new_and_drains_or_fails_pending():
    svc = ScoringService(Scorer(CenterSnapshot(0, _centers()),
                                backend="jnp"), ServiceConfig())
    f = svc.submit(np.zeros((8, D), np.float32))
    svc.close()                          # drain=True serves the pending
    assert f.result(10).assignments.shape == (8,)
    with pytest.raises(ServiceClosed):
        svc.submit(np.zeros((8, D), np.float32))


def test_submit_validates_shape_fast():
    svc = ScoringService(Scorer(CenterSnapshot(0, _centers()),
                                backend="jnp"), ServiceConfig())
    with svc:
        with pytest.raises(ValueError, match="dim"):
            svc.submit(np.zeros((4, D + 1), np.float32))
        with pytest.raises(ValueError):
            svc.submit(np.zeros((0, D), np.float32))
        # a 1-row vector request is promoted to (1, d)
        assert svc.score(np.zeros((D,), np.float32),
                         timeout=30).assignments.shape == (1,)


# ------------------------------------------------------------- hot swap --

def test_hot_swap_mid_traffic_no_torn_reads():
    """Every response is scored against exactly one snapshot version:
    under concurrent swaps, assignments must match that version's
    reference bit-for-bit; after the last swap, responses switch to the
    newest snapshot within one batch."""
    base = _centers(c=6, seed=3)
    versions = {v: np.roll(base, v, axis=0) for v in range(4)}
    refs = {v: make_assigner(c, backend="jnp") for v, c in versions.items()}
    svc = ScoringService(
        [Scorer(CenterSnapshot(0, base), backend="jnp", replica=f"r{i}")
         for i in range(2)],
        ServiceConfig(max_batch_rows=256, bucket_base=64))
    reqs = _reqs(120, lo=4, hi=120, seed=5)
    results = []
    stop = threading.Event()

    def swapper():
        v = 0
        while not stop.is_set():
            v = (v + 1) % 4
            svc.swap(v, versions[v])
            time.sleep(0.002)

    t = threading.Thread(target=swapper)
    t.start()
    try:
        futs = [svc.submit(r) for r in reqs]
        results = [f.result(30) for f in futs]
    finally:
        stop.set()
        t.join()
    for r, res in zip(reqs, results):
        assert res.version in versions
        assert np.array_equal(res.assignments,
                              np.asarray(refs[res.version](r))), \
            f"torn read: response does not match version {res.version}"
    # final swap: the very next dispatched batch sees the new snapshot
    svc.swap(99, versions[1])
    assert svc.score(reqs[0], timeout=30).version == 99
    svc.close()


def test_swap_handles_grown_and_shrunk_center_counts():
    svc = ScoringService(Scorer(CenterSnapshot(0, _centers(c=4)),
                                backend="jnp"),
                         ServiceConfig(max_batch_rows=128))
    with svc:
        x = RNG.normal(size=(32, D)).astype(np.float32)
        svc.swap(1, _centers(c=7, seed=9))       # grown
        assert int(svc.score(x, 30).assignments.max()) <= 6
        svc.swap(2, _centers(c=3, seed=9))       # shrunk
        assert int(svc.score(x, 30).assignments.max()) <= 2


# ------------------------------------------------------ compile economy --

def test_assign_store_ragged_tail_compiles_one_program():
    """The satellite fix: a store whose tail chunk is short used to
    compile two programs (full + ragged shape); padding the tail to the
    chunk shape makes it one."""
    x = RNG.normal(size=(1000, D)).astype(np.float32)   # 3×300 + 100 tail
    store = ChunkStore.ingest(x, chunk_rows=300)
    centers = _centers()
    fn = make_assigner(centers, backend="jnp")
    out = np.concatenate(list(assign_store(store, centers, assigner=fn)))
    assert fn.traces == 1
    assert out.shape == (1000,)
    # parity with direct scoring (phantom rows sliced back off)
    assert np.array_equal(out, np.asarray(make_assigner(
        centers, backend="jnp")(x)))


def test_service_compiles_once_per_bucket():
    svc = ScoringService(Scorer(CenterSnapshot(0, _centers()),
                                backend="jnp"),
                         ServiceConfig(max_batch_rows=256, bucket_base=64))
    with svc:
        for r in _reqs(60, lo=1, hi=250, seed=7):
            svc.score(r, timeout=30)
        traces = svc.compile_counts()["r0"]
    assert traces <= len(svc.buckets)    # one program per bucket, max


# ------------------------------------------------- snapshots/publishing --

def test_publisher_follows_stream_and_persists_manifest():
    """Learner → publisher → replicas + checkpoint: scorers follow each
    ingest's snapshot; a replica in another process boots the latest
    version from the self-describing manifest (grown/shrunk C safe)."""
    cfg = StreamConfig(n_clusters=3, window=2, driver_sample=64,
                       max_iter=40, backend="jnp", seed=0)
    model = StreamingBigFCM(cfg)
    with tempfile.TemporaryDirectory() as tmp:
        ckpt = CheckpointManager(tmp, async_save=False)
        pub = SnapshotPublisher(ckpt=ckpt)
        model.add_snapshot_listener(pub.publish)
        rng = np.random.default_rng(2)
        rep = None
        for _ in range(3):
            rep = model.ingest(rng.normal(size=(256, D)).astype(np.float32))
        # a scorer attached late catches up to the latest snapshot
        s = Scorer(CenterSnapshot(-1, np.zeros((1, D), np.float32)),
                   backend="jnp", replica="late")
        pub.attach(s)
        assert s.version == rep.step
        np.testing.assert_array_equal(
            np.asarray(pub.latest().centers),
            np.asarray(model.state.centers))
        # manifest boot path — shapes come from the manifest, no template
        boot = snapshot_from_checkpoint(ckpt)
        assert boot.version == rep.step
        np.testing.assert_array_equal(boot.centers,
                                      np.asarray(model.state.centers))
        assert boot.weights is not None
        # grown center count round-trips as-is
        pub.publish(100, _centers(c=9, seed=4))
        assert snapshot_from_checkpoint(ckpt).centers.shape == (9, D)
        assert s.version == 100


def test_restore_arrays_template_free():
    with tempfile.TemporaryDirectory() as tmp:
        ckpt = CheckpointManager(tmp, async_save=False)
        ckpt.save(5, {"centers": _centers(c=4), "extra": np.arange(3)})
        arrs = ckpt.restore_arrays()
        assert set(arrs) == {"centers", "extra"}
        assert arrs["centers"].shape == (4, D)
        with pytest.raises(FileNotFoundError):
            CheckpointManager(tmp + "/empty").restore_arrays()


# ------------------------------------------------------------ obs labels --

def test_per_replica_labels_and_aggregate_histogram():
    svc = ScoringService(
        [Scorer(CenterSnapshot(0, _centers()), backend="jnp",
                replica=f"r{i}") for i in range(2)],
        ServiceConfig(max_batch_rows=128))
    with svc:
        futs = [svc.submit(r) for r in _reqs(40, seed=11, hi=100)]
        total = sum(f.result(30).assignments.shape[0] for f in futs)
    snap = obs.metrics_snapshot()
    # the unlabeled aggregate the SLO reads, plus per-replica series
    agg = snap["histograms"]["span.serve.assign"]
    assert agg["count"] > 0 and np.isfinite(agg["p99"])
    per = [k for k in snap["histograms"]
           if k.startswith("span.serve.assign{replica=")]
    assert per                                   # at least one replica
    assert sum(snap["histograms"][k]["count"] for k in per) \
        == agg["count"]
    rec = [v for k, v in snap["counters"].items()
           if k.startswith("serve.records{replica=")]
    assert sum(rec) == total
    # e2e request latency histogram resolves per response
    assert snap["histograms"]["serve.request"]["count"] == len(futs)


# -------------------------------------------------------- profiler spans --

def test_scoring_path_spans_on_the_profiler_trace(tmp_path):
    """Under the profiler, each turn of the worker's loop leaves on the
    worker's line a ``serve.take``, a ``serve.pack`` where it took a
    batch, a ``serve.assign``, and a ``serve.resolve`` where a batch was
    in flight, in that order.  Inside the ``serve.assign`` come the new
    batch's ``serve.upload`` and ``serve.launch``, then the fetch of the
    batch in flight, ``serve.fetch``.  A submit that waits for queue
    room leaves a ``serve.admit`` on the caller's line."""
    from conftest import host_profile

    scorer = GatedScorer(CenterSnapshot(0, _centers()), backend="jnp")
    scorer.gate.set()
    reqs = _reqs(12, lo=20, hi=33, seed=12)
    cfg = ServiceConfig(max_batch_rows=64, bucket_base=64, queue_rows=64)
    with ScoringService(scorer, cfg) as warm:      # compile first
        warm.score(reqs[0], timeout=30)
    scorer.gate.clear()                            # back the queue up
    opener = threading.Timer(0.3, scorer.gate.set)

    def run():
        svc = ScoringService(scorer, cfg)          # its worker starts
        opener.start()                             # inside the trace
        futs = [svc.submit(r) for r in reqs]
        for f in futs:
            f.result(30)
        _close_within(svc)

    events = host_profile(run, tmp_path)
    opener.join(10)
    batches = int(obs.counter("serve.batches", replica="r0").value) - 1
    stages = ("serve.take", "serve.pack", "serve.assign", "serve.resolve")
    worker = sorted((s, -d, n) for line, n, s, d in events
                    if n.startswith("serve.") and n != "serve.admit")
    lines = {line for line, n, _, _ in events
             if n.startswith("serve.") and n != "serve.admit"}
    assert len(lines) == 1                         # one worker thread
    turns, inner = [], []
    for s, d, n in worker:
        if n == "serve.take":
            turns.append([n])
        elif n in stages:
            turns[-1].append(n)
            if n == "serve.assign":
                assign = (s, s - d)
                inner.append([])
        else:                      # upload, launch, fetch: in the assign
            assert turns[-1][-1] == "serve.assign", n
            assert assign[0] <= s and s - d <= assign[1], n
            assert not inner[-1] or inner[-1][-1][1] <= s, n
            inner[-1].append((n, s - d))
    # the last take is the one that found the service closed
    assert turns.pop() == ["serve.take"]
    held = False
    for turn, spans in zip(turns, inner):
        packed = "serve.pack" in turn
        assert packed or held
        assert turn == (["serve.take"] + ["serve.pack"] * packed
                        + ["serve.assign"] + ["serve.resolve"] * held)
        assert [n for n, _ in spans] == (
            ["serve.upload", "serve.launch"] * packed
            + ["serve.fetch"] * held)
        held = packed
    assert not held
    assert sum("serve.pack" in t for t in turns) == batches
    overlapped = sum("serve.pack" in t and "serve.resolve" in t
                     for t in turns)
    assert overlapped >= 1           # the backed-up queue overlapped
    assert obs.counter("serve.overlapped",
                       replica="r0").value == overlapped
    admits = [(line, n) for line, n, _, _ in events if n == "serve.admit"]
    assert admits and not {line for line, _ in admits} & lines


# ------------------------------------------------- one batch in flight --

def _close_within(svc, seconds=10.0):
    """``svc.close()``, failing the test where it, or a worker, hangs."""
    closer = threading.Thread(target=svc.close, daemon=True)
    closer.start()
    closer.join(seconds)
    assert not closer.is_alive(), "close() hung"
    assert not any(t.is_alive() for t in svc._threads)


def _overlap_counts(replica):
    return (int(obs.counter("serve.batches", replica=replica).value),
            int(obs.counter("serve.overlapped", replica=replica).value))


def test_batches_in_flight_answer_bit_for_bit_in_fifo_order():
    """With the queue backed up, every batch after the first launches
    while the one before it is in flight; the answers still equal
    per-request scoring bit for bit and resolve in FIFO order."""
    centers = _centers()
    scorer = GatedScorer(CenterSnapshot(0, centers), backend="jnp")
    svc = ScoringService(scorer, ServiceConfig(max_batch_rows=128,
                                               bucket_base=32))
    reqs = _reqs(40, lo=1, hi=100, seed=21)
    order = []
    futs = [svc.submit(r) for r in reqs]
    for i, f in enumerate(futs):
        f.add_done_callback(lambda _f, i=i: order.append(i))
    scorer.gate.set()
    results = [f.result(10) for f in futs]
    _close_within(svc)
    ref = make_assigner(centers, backend="jnp")
    for r, res in zip(reqs, results):
        assert np.array_equal(res.assignments, np.asarray(ref(r)))
        assert res.version == 0
    assert order == list(range(len(reqs)))
    batches, overlapped = _overlap_counts("r0")
    assert batches > 2 and overlapped == batches - 1


def test_lone_request_is_answered_without_a_later_arrival():
    """A request followed by an idle queue is answered at once: with a
    batch in flight and nothing queued the worker fetches instead of
    waiting for more."""
    svc = ScoringService(Scorer(CenterSnapshot(0, _centers()),
                                backend="jnp"),
                         ServiceConfig(max_batch_rows=64, bucket_base=64))
    x = RNG.normal(size=(10, D)).astype(np.float32)
    svc.score(x, timeout=30)                       # compile first
    t0 = time.monotonic()
    res = svc.submit(x).result(5)                  # nothing follows it
    assert time.monotonic() - t0 < 2.0
    assert res.assignments.shape == (10,)
    _close_within(svc)


def test_overlapped_counter_stays_zero_for_sequential_calls():
    """``serve.overlapped`` counts only batches launched while another
    was in flight: strictly sequential calls never overlap."""
    svc = ScoringService(Scorer(CenterSnapshot(0, _centers()),
                                backend="jnp"),
                         ServiceConfig(max_batch_rows=64, bucket_base=64))
    for r in _reqs(8, lo=1, hi=60, seed=22):
        svc.score(r, timeout=10)
    _close_within(svc)
    assert _overlap_counts("r0") == (8, 0)


class _Unfetchable:
    """Answers whose copy back fails."""

    def copy_to_host_async(self):
        pass

    def __array__(self, *a, **k):
        raise ValueError("fetch failed")


class FaultyScorer(GatedScorer):
    """Gated; its second call fails at launch (``fault="launch"``), or
    its first call's answers fail to fetch (``fault="fetch"``)."""

    def __init__(self, *a, fault, **k):
        self.fault, self.calls = fault, 0
        super().__init__(*a, **k)

    def score(self, x, snap=None):
        self.calls += 1
        if self.fault == "launch" and self.calls == 2:
            raise ValueError("launch failed")
        res = super().score(x, snap)
        return (_Unfetchable() if self.fault == "fetch" and self.calls == 1
                else res)


@pytest.mark.parametrize("fault, answered", [("launch", 1), ("fetch", 0)])
def test_failure_with_a_batch_in_flight_resolves_every_future(fault,
                                                              answered):
    """Three one-request batches: batch 1 in flight while batch 2
    launches, batch 3 queued.  A failed launch of batch 2 still answers
    batch 1; a failed fetch of batch 1 fails batches 1 and 2.  Either
    way the rest fails with the same error, nothing hangs, and the
    service is closed to new requests."""
    scorer = FaultyScorer(CenterSnapshot(0, _centers()), backend="jnp",
                          fault=fault)
    svc = ScoringService(scorer, ServiceConfig(max_batch_rows=64))
    x = RNG.normal(size=(64, D)).astype(np.float32)
    futs = [svc.submit(x)]
    assert scorer.entered.wait(10)                 # batch 1 launching
    futs += [svc.submit(x), svc.submit(x)]
    scorer.gate.set()
    for f in futs[:answered]:
        assert f.result(10).assignments.shape == (64,)
    for f in futs[answered:]:
        with pytest.raises(ValueError, match=f"{fault} failed"):
            f.result(10)
    with pytest.raises(RuntimeError, match="failed"):
        svc.submit(x)
    _close_within(svc)


def _fleet(seed, version):
    rng = np.random.default_rng(seed)
    return tenant_set([f"u{i}" for i in range(4)],
                      (rng.normal(size=(4, 5, D)) * 4).astype(np.float32),
                      np.ones((4, 5), np.float32),
                      versions=version + np.arange(4))


def test_tenant_service_overlaps_batches_across_tenants_and_a_swap():
    """The tenant service runs the same worker loop: mixed-tenant
    batches launch while another is in flight.  A swap made while the
    first batch is in flight leaves that batch on the old fleet and
    reaches every later one; each answer matches its tenant's centers in
    the fleet of its version, in FIFO order."""
    old, new = _fleet(1, 0), _fleet(2, 100)
    scorer = GatedTenantScorer(old, replica="t0")
    svc = TenantScoringService(scorer, ServiceConfig(max_batch_rows=64,
                                                     bucket_base=16))
    rng = np.random.default_rng(23)
    sent = [("u0", rng.normal(size=(64, D)).astype(np.float32))]
    futs = [svc.submit(*sent[0])]
    assert scorer.entered.wait(10)                 # batch 1 read `old`
    svc.swap(new)
    for i in range(24):
        sent.append((f"u{i % 4}", rng.normal(
            size=(int(rng.integers(1, 40)), D)).astype(np.float32)))
        futs.append(svc.submit(*sent[-1]))
    order = []
    for i, f in enumerate(futs):
        f.add_done_callback(lambda _f, i=i: order.append(i))
    scorer.gate.set()
    results = [f.result(10) for f in futs]
    _close_within(svc)
    refs = {0: TenantScorer(old), 100: TenantScorer(new)}
    for k, ((tenant, x), res) in enumerate(zip(sent, results)):
        fleet = 0 if k == 0 else 100
        want, version = refs[fleet].assign(tenant, x)
        assert res.version == version
        assert np.array_equal(res.assignments, want)
    assert order == list(range(len(futs)))
    batches, overlapped = _overlap_counts("t0")
    assert batches > 2 and overlapped == batches - 1


# ------------------------------------------ large C: the tiled kernel ---

def test_service_scores_large_c_through_the_tiled_kernel(monkeypatch):
    """At C = 2,048 and d = 128 (an IVF coarse quantizer's width) the
    ``pallas`` backend's hard labels come from the C-tiled
    nearest-center kernel (interpret mode here): the rule is held at
    1,024 centers, whatever the constant says, and told it is on a TPU.
    Every label names the
    float64 nearest center, and ``serve.tiled`` counts every batch."""
    from repro.kernels import ops
    from repro.kernels.ref import nearest_center_ref, nearest_center_right

    monkeypatch.setattr(ops, "NEAREST_MIN_CENTERS", 1024)
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    rng = np.random.default_rng(15)
    c, d = 2048, 128
    centers = rng.normal(size=(c, d)).astype(np.float32)
    centers[1000] = centers[10]              # a duplicated center
    scorer = Scorer(CenterSnapshot(0, centers), backend="pallas")
    svc = ScoringService(scorer, ServiceConfig(max_batch_rows=256,
                                               bucket_base=128))
    reqs = [(centers[rng.integers(0, c, size=n)]
             + 0.3 * rng.normal(size=(n, d))).astype(np.float32)
            for n in (1, 37, 128, 200, 300)]
    reqs.append(centers[[10, 1000]])
    futs = [svc.submit(r) for r in reqs]
    results = [f.result(120) for f in futs]
    svc.close()
    for r, res in zip(reqs, results):
        assert nearest_center_right(r, centers, res.assignments).all()
        want, _ = nearest_center_ref(r, centers)
        np.testing.assert_array_equal(res.assignments, want)
    assert list(results[-1].assignments) == [10, 10]
    batches = int(obs.counter("serve.batches", replica="r0").value)
    assert batches >= 1
    assert int(obs.counter("serve.tiled", replica="r0").value) == batches


def test_small_c_keeps_the_jnp_path(monkeypatch):
    """Below the rule's center count the ``pallas`` backend answers with
    the jnp path, on a TPU too: no batch is counted as tiled."""
    from repro.kernels import ops

    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    centers = _centers(c=23)
    scorer = Scorer(CenterSnapshot(0, centers), backend="pallas")
    assert not scorer.tiled
    with ScoringService(scorer, ServiceConfig(max_batch_rows=128,
                                              bucket_base=32)) as svc:
        reqs = _reqs(5, seed=4)
        results = [svc.score(r, 30) for r in reqs]
    ref = make_assigner(centers, backend="jnp")
    for r, res in zip(reqs, results):
        assert np.array_equal(res.assignments, np.asarray(ref(r)))
    assert int(obs.counter("serve.batches", replica="r0").value) == 5
    assert int(obs.counter("serve.tiled", replica="r0").value) == 0


# ---------------------------------------------- full batches: the split --

class _Recording(ScoringService):
    """Records each packed batch's parts as ``(rows, version)``."""

    def __init__(self, *a, **k):
        self.packed = []
        super().__init__(*a, **k)

    def _pack(self, scorer, reqs):
        batch = super()._pack(scorer, reqs)
        self.packed.append([(r.n, v) for r, v in zip(reqs, batch.versions)])
        return batch


class StepScorer(GatedScorer):
    """Gated; runs ``hooks[k]()`` as its k-th call starts, and fails its
    ``fail_at``-th call at launch."""

    def __init__(self, *a, hooks=None, fail_at=None, **k):
        self.hooks, self.fail_at, self.calls = hooks or {}, fail_at, 0
        super().__init__(*a, **k)

    def score(self, x, snap=None):
        self.calls += 1
        self.hooks.get(self.calls, lambda: None)()
        if self.calls == self.fail_at:
            raise ValueError("launch failed")
        return super().score(x, snap)


def _backed_up(svc, scorer, sizes, seed=31):
    """Submit a plug of ``max_batch_rows`` rows, wait until the gated
    worker holds it, then queue requests of ``sizes`` rows behind it;
    returns the requests (plug first), their futures and the order in
    which the futures resolve, each with the batch that answered it."""
    rng = np.random.default_rng(seed)
    reqs = [rng.normal(size=(int(n), D)).astype(np.float32)
            for n in [svc.cfg.max_batch_rows, *sizes]]
    order = []
    batches = obs.counter("serve.batches", replica=scorer.replica)
    before = int(batches.value)
    futs = [svc.submit(reqs[0])]
    assert scorer.entered.wait(10)
    futs += [svc.submit(r) for r in reqs[1:]]
    for i, f in enumerate(futs):
        f.add_done_callback(
            lambda _f, i=i: order.append((i, int(batches.value) - before)))
    return reqs, futs, order


def _head_run(sizes, m):
    """The strict FIFO head run, in pure Python: the batch (from 1) that
    answers each request of ``sizes``, each batch taking its head whole
    and then the next requests while they fit ``m`` rows."""
    out, batch, rows = [], 0, m
    for n in sizes:
        if rows + n > m:
            batch, rows = batch + 1, 0
        rows += n
        out.append(batch)
    return out


def _splits(sizes, m):
    """Requests cut by a batch boundary when every batch holds ``m``
    rows (every size at most ``m``)."""
    ends = np.cumsum(sizes)
    return int(np.sum((ends - np.asarray(sizes)) // m < (ends - 1) // m))


def test_split_batches_are_full_and_answer_bit_for_bit_in_fifo_order():
    """A backed-up queue of requests that do not tile the batch: the
    request that stops each batch is split, so every batch but the last
    holds ``max_batch_rows`` rows; every answer equals per-request
    scoring bit for bit, with one version, in FIFO order."""
    centers = _centers()
    scorer = GatedScorer(CenterSnapshot(0, centers), backend="jnp")
    svc = _Recording(scorer, ServiceConfig(max_batch_rows=128,
                                           bucket_base=32))
    reqs, futs, order = _backed_up(svc, scorer,
                                   np.random.default_rng(32).integers(
                                       1, 100, size=60))
    scorer.gate.set()
    results = [f.result(10) for f in futs]
    _close_within(svc)
    ref = make_assigner(centers, backend="jnp")
    for r, res in zip(reqs, results):
        assert np.array_equal(res.assignments, np.asarray(ref(r)))
        assert res.version == 0
    assert [i for i, _ in order] == list(range(len(reqs)))
    rows = [sum(n for n, _ in parts) for parts in svc.packed]
    assert rows[:-1] == [128] * (len(rows) - 1) and 0 < rows[-1] <= 128
    assert sum(rows) == sum(len(r) for r in reqs)


@seeded_cases(lambda rng: (int(rng.integers(32, 200)),
                           rng.integers(1, 400, size=int(
                               rng.integers(5, 40)))), n=8)
def test_no_request_answers_later_than_under_the_head_run(case):
    """Against a pure-Python model of the strict FIFO head run, over
    random sizes (some larger than a batch): no request is answered in
    a later batch, and a batch is short only where its head is a whole
    request larger than the room, or the queue ran dry."""
    m, sizes = case
    scorer = GatedScorer(CenterSnapshot(0, _centers()), backend="jnp")
    svc = _Recording(scorer, ServiceConfig(max_batch_rows=m,
                                           bucket_base=32))
    reqs, futs, order = _backed_up(svc, scorer, sizes)
    scorer.gate.set()
    for f in futs:
        f.result(30)
    _close_within(svc)
    head_run = _head_run([len(r) for r in reqs], m)
    for i, batch in order:
        assert batch <= head_run[i], (i, batch, head_run[i])
    rows = [sum(n for n, _ in parts) for parts in svc.packed]
    for parts, n in zip(svc.packed[:-1], rows):
        assert n == m or (len(parts) == 1 and n > m), svc.packed


def test_swap_between_a_split_requests_parts_scores_it_whole_again():
    """A swap lands after the batch holding a request's first part was
    packed: the next batch scores the whole request against the new
    snapshot, so it answers with one version, and that version's
    centers; the first part's rows are scored twice."""
    old, new = _centers(seed=1), _centers(seed=2)
    svc = None
    scorer = StepScorer(CenterSnapshot(0, old), backend="jnp",
                        hooks={2: lambda: svc.swap(1, new)})
    svc = _Recording(scorer, ServiceConfig(max_batch_rows=128,
                                           bucket_base=32))
    reqs, futs, _ = _backed_up(svc, scorer, [100, 60, 20])
    scorer.gate.set()
    results = [f.result(10) for f in futs]
    _close_within(svc)
    refs = {0: make_assigner(old, backend="jnp"),
            1: make_assigner(new, backend="jnp")}
    assert [res.version for res in results] == [0, 0, 1, 1]
    for r, res in zip(reqs, results):
        assert np.array_equal(res.assignments,
                              np.asarray(refs[res.version](r)))
    # batch 2 held the first 28 rows of the 60-row request; batch 3,
    # packed with its other 32 against the new snapshot, was packed
    # again with all 60
    assert svc.packed[1:] == [[(100, 0), (28, 0)], [(32, 1), (20, 1)],
                              [(60, 1), (20, 1)]]
    assert obs.counter("serve.records", replica="r0").value == 128 + 208
    assert obs.counter("serve.split", replica="r0").value == 1


@pytest.mark.parametrize("fail_at, answered", [(2, 1), (3, 2)])
def test_failure_with_a_split_request_held_resolves_every_future(
        fail_at, answered):
    """Batch 2 holds the first part of the third request, batch 3 its
    rest.  A failed launch of either batch fails that request, and
    everything after it, with the error; what came before is answered,
    nothing hangs, and ``close()`` returns."""
    scorer = StepScorer(CenterSnapshot(0, _centers()), backend="jnp",
                        fail_at=fail_at)
    svc = ScoringService(scorer, ServiceConfig(max_batch_rows=64))
    _, futs, _ = _backed_up(svc, scorer, [40, 40, 10])
    scorer.gate.set()
    for f in futs[:answered]:
        assert f.result(10).assignments.shape[0] > 0
    for f in futs[answered:]:
        with pytest.raises(ValueError, match="launch failed"):
            f.result(10)
    _close_within(svc)


def test_close_with_drain_answers_a_carried_rest():
    """``close(drain=True)`` made while the queue is backed up: the
    worker splits a request after the service has closed, carries its
    rest into its next batch, and answers it."""
    centers = _centers()
    scorer = GatedScorer(CenterSnapshot(0, centers), backend="jnp")
    svc = ScoringService(scorer, ServiceConfig(max_batch_rows=64))
    reqs, futs, _ = _backed_up(svc, scorer, [40, 40])
    closer = threading.Thread(target=svc.close, daemon=True)
    closer.start()
    while not svc._closed:
        time.sleep(0.001)
    scorer.gate.set()
    closer.join(10)
    assert not closer.is_alive(), "close() hung"
    ref = make_assigner(centers, backend="jnp")
    for r, f in zip(reqs, futs):
        assert np.array_equal(f.result(0).assignments, np.asarray(ref(r)))
    assert obs.counter("serve.split", replica="r0").value == 1


def test_no_coalescing_splits_nothing():
    """``coalesce=False`` scores each request alone at its own shape,
    however its rows fall against ``max_batch_rows``."""
    centers = _centers()
    scorer = GatedScorer(CenterSnapshot(0, centers), backend="jnp")
    svc = _Recording(scorer, ServiceConfig(max_batch_rows=64,
                                           coalesce=False))
    sizes = [40, 40, 100, 7]
    reqs, futs, _ = _backed_up(svc, scorer, sizes)
    scorer.gate.set()
    ref = make_assigner(centers, backend="jnp")
    for r, f in zip(reqs, futs):
        assert np.array_equal(f.result(10).assignments, np.asarray(ref(r)))
    _close_within(svc)
    assert [[n for n, _ in parts] for parts in svc.packed] == [
        [64], *([n] for n in sizes)]
    assert obs.counter("serve.split", replica="r0").value == 0


@pytest.mark.parametrize("m", [64, 100])
def test_split_counter_counts_requests_cut_by_a_batch(m):
    """``serve.split`` counts the requests split across two batches: on
    a backed-up queue, one for each batch boundary that falls inside a
    request; a request that ends a batch exactly is not split."""
    scorer = GatedScorer(CenterSnapshot(0, _centers()), backend="jnp")
    svc = ScoringService(scorer, ServiceConfig(max_batch_rows=m,
                                               bucket_base=32))
    sizes = list(np.random.default_rng(33).integers(1, m, size=40))
    sizes[:3] = [m - 10, 10, m]           # two ends on a boundary
    _, futs, _ = _backed_up(svc, scorer, sizes)
    scorer.gate.set()
    for f in futs:
        f.result(10)
    _close_within(svc)
    want = _splits(sizes, m)
    assert want > 0
    assert obs.counter("serve.split", replica="r0").value == want
    assert obs.counter("serve.batches", replica="r0").value == (
        1 + -(-sum(sizes) // m))
