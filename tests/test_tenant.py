"""Tenant plane (PR 10): batched multi-model fit parity, compile-count
proof, tenant-routed serving with never-tear versions, the per-group
fairness cap, the stacked checkpoint round-trip, and the one-pass
ChunkStore column stats.

The load-bearing claims, each pinned here:
  * every tenant of a batched `fit_tenants` reproduces its own
    per-tenant fit (mixed row counts, mixed seeds, mixed fuzzifiers) to
    ≤1e-5 relative objective;
  * one compiled program per (row bucket, tenant bucket, backend)
    regardless of how many fits or tenant counts pass through;
  * a `TenantSet` round-trips a checkpoint bit-identically at T=1 and
    at a non-bucket-aligned T=257, and restores subsets by id;
  * the ``max_group_rows`` fairness cap stops a firehose tenant from
    starving a quiet one (and ``None`` preserves strict FIFO runs).
"""
import tempfile
import threading
import time

import numpy as np
import pytest

from repro import obs
from repro.core import fcm
from repro.data import ChunkStore, geom_bucket
from repro.engine import batched_trace_counts
from repro.ft import CheckpointManager
from repro.serve import (ScoringService, ServiceConfig, TenantScorer,
                         TenantScoringService, tenant_snapshot)
from repro.tenant import (TenantFitConfig, TenantSet, fit_tenants,
                          fit_tenants_looped, load_tenants, save_tenants,
                          tenant_set)

D = 3


@pytest.fixture(autouse=True)
def _fresh_obs():
    obs.reset_all()
    yield
    obs.reset_all()


def _cohort(t, seed=0, lo=8, hi=180, d=D):
    """Mixed-size per-tenant record sets around distinct blob centers."""
    rng = np.random.default_rng(seed)
    return {f"t{i}": (rng.normal(size=(int(rng.integers(lo, hi)), d))
                      + 3.0 * (i % 5)).astype(np.float32)
            for i in range(t)}


CFG = TenantFitConfig(n_clusters=3, seed=11, backend="jnp")


# ---------------------------------------------------------------- parity --

def test_batched_matches_looped_per_tenant():
    data = _cohort(9, seed=1)
    b = fit_tenants(data, CFG)
    l = fit_tenants_looped(data, CFG)
    assert b.ids == l.ids
    rel = (np.abs(b.objective - l.objective)
           / np.maximum(np.abs(l.objective), 1e-12))
    assert np.all(rel <= 1e-5), rel          # the acceptance bar
    np.testing.assert_allclose(b.centers, l.centers, rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(b.n_iter, l.n_iter)


def test_batched_matches_looped_mixed_fuzzifiers():
    data = _cohort(6, seed=2)
    m_t = np.asarray([1.5, 2.0, 2.5, 3.0, 1.7, 2.2], np.float32)
    b = fit_tenants(data, CFG, m_t=m_t)
    l = fit_tenants_looped(data, CFG, m_t=m_t)
    rel = (np.abs(b.objective - l.objective)
           / np.maximum(np.abs(l.objective), 1e-12))
    assert np.all(rel <= 1e-5), rel


def test_batched_tenant_matches_single_fcm():
    """Row t of the batch == that tenant's own `core.fcm` run on its
    UNPADDED records (phantom rows and phantom tenants change nothing)."""
    data = _cohort(4, seed=3)
    b = fit_tenants(data, CFG)
    from repro.tenant import seed_centers
    from repro.tenant.core import normalize_tenant_data
    ids, xs = normalize_tenant_data(data)
    seeds = seed_centers(xs, CFG)
    for i, tid in enumerate(ids):
        solo = fcm(xs[i], seeds[i], m=CFG.m, eps=CFG.eps,
                   max_iter=CFG.max_iter, backend="jnp")
        rel = (abs(float(b.objective[i]) - float(solo.objective))
               / max(abs(float(solo.objective)), 1e-12))
        # looser bar than batched-vs-looped: padded vs UNPADDED
        # reduction order can move the eps crossing by one sweep (the
        # padded looped baseline above matches to 1e-5)
        assert rel <= 1e-4, (tid, rel)
        assert abs(int(b.n_iter[i]) - int(solo.n_iter)) <= 1


# ---------------------------------------------------------- compile count --

def test_one_program_per_bucket_regardless_of_tenant_count():
    # d=7 guarantees shapes no earlier test compiled (the jit cache is
    # process-global — exactly the property under test)
    before = set(batched_trace_counts())
    # T=5 and T=7 share the tenant bucket (8); rows 8..120 share the
    # row bucket (128): ONE compiled program serves both fits.
    fit_tenants(_cohort(5, seed=4, lo=8, hi=120, d=7), CFG)
    fit_tenants(_cohort(7, seed=5, lo=8, hi=120, d=7), CFG)
    counts = {k: v for k, v in batched_trace_counts().items()
              if k not in before}
    assert len(counts) == 1, counts
    (key, n), = counts.items()
    assert n == 1, counts                       # traced exactly once
    assert key[1] == geom_bucket(7, base=CFG.tenant_base)
    assert key[3] == 7
    # a different row bucket is a NEW program (by design, one per bucket)
    fit_tenants(_cohort(5, seed=6, lo=200, hi=250, d=7), CFG)
    assert len([k for k in batched_trace_counts()
                if k not in before]) == 2


# ------------------------------------------------------------- checkpoint --

def _random_tenant_set(t, seed=0, c=4, d=5):
    rng = np.random.default_rng(seed)
    return tenant_set([f"u{i}" for i in range(t)],
                      rng.normal(size=(t, c, d)).astype(np.float32),
                      rng.uniform(1, 9, size=(t, c)).astype(np.float32),
                      versions=rng.integers(0, 99, size=t),
                      objective=rng.normal(size=t).astype(np.float32),
                      n_iter=rng.integers(1, 50, size=t))


@pytest.mark.parametrize("t", [1, 257])   # 257: NOT bucket-aligned
def test_tenant_checkpoint_roundtrip_bit_identical(t):
    ts = _random_tenant_set(t, seed=t)
    with tempfile.TemporaryDirectory() as d:
        ckpt = CheckpointManager(d, async_save=False)
        save_tenants(ckpt, 3, ts)
        back = load_tenants(ckpt)
    assert back.ids == ts.ids
    for a, b in zip(back[1:], ts[1:]):    # every stacked array leaf
        np.testing.assert_array_equal(a, b)
        assert a.dtype == b.dtype


def test_tenant_checkpoint_subset_restore():
    ts = _random_tenant_set(40, seed=7)
    with tempfile.TemporaryDirectory() as d:
        ckpt = CheckpointManager(d, async_save=False)
        save_tenants(ckpt, 1, ts)
        sub = load_tenants(ckpt, tenants=["u31", "u0", "u7"])
        with pytest.raises(KeyError):
            load_tenants(ckpt, tenants=["nope"])
    assert sub.ids == ("u31", "u0", "u7")
    for tid in sub.ids:
        i, j = sub.index(tid), ts.index(tid)
        np.testing.assert_array_equal(sub.centers[i], ts.centers[j])
        assert int(sub.versions[i]) == int(ts.versions[j])


def test_restore_arrays_keys_filter():
    with tempfile.TemporaryDirectory() as d:
        ckpt = CheckpointManager(d, async_save=False)
        ckpt.save(0, {"a": np.arange(3), "b": np.arange(4),
                      "c": np.arange(5)})
        arrs = ckpt.restore_arrays(0, keys=["a", "c", "missing"])
    assert sorted(arrs) == ["a", "c"]     # missing keys simply absent
    np.testing.assert_array_equal(arrs["c"], np.arange(5))


# ------------------------------------------------------- tenant serving --

def test_tenant_service_routes_and_reports_per_tenant_versions():
    data = _cohort(6, seed=8)
    ts = fit_tenants(data, CFG)
    ts = ts._replace(versions=np.arange(10, 16, dtype=np.int64))
    scorer = TenantScorer(ts, replica="tA")
    with TenantScoringService(scorer,
                              ServiceConfig(max_batch_rows=256)) as svc:
        futs = {t: svc.submit(t, data[t][:9]) for t in data}
        for t, f in futs.items():
            res = f.result(30)
            # routed, coalesced scoring == that tenant's own assignment
            direct, version = scorer.assign(t, data[t][:9])
            np.testing.assert_array_equal(res.assignments, direct)
            assert res.version == version == 10 + ts.index(t)
        with pytest.raises(KeyError):
            svc.submit("ghost", data["t0"][:2])


def test_tenant_hot_swap_never_tears():
    """Each response's rows score against exactly ONE fleet snapshot:
    under constant swapping, a response is entirely old or entirely
    new — version always matches its tenant's row in SOME snapshot."""
    ts0 = _random_tenant_set(4, seed=9, d=D)
    scorer = TenantScorer(ts0)
    stop = threading.Event()

    def swapper():
        v = 100
        while not stop.is_set():
            bumped = ts0._replace(versions=np.full(4, v, np.int64))
            scorer.swap(tenant_snapshot(bumped))
            v += 1
            time.sleep(0.001)
    th = threading.Thread(target=swapper, daemon=True)
    th.start()
    try:
        with TenantScoringService(scorer) as svc:
            rng = np.random.default_rng(0)
            for _ in range(30):
                res = svc.score("u2", rng.normal(size=(17, D)), timeout=30)
                assert (res.version == int(ts0.versions[2])
                        or res.version >= 100)
    finally:
        stop.set()
        th.join()


class GatedTenantScorer(TenantScorer):
    """Blocks every score call on an event — backs the queue up so
    batch composition is deterministic (the `GatedScorer` idiom)."""

    def __init__(self, *a, **k):
        self.gate = threading.Event()
        self.entered = threading.Event()
        super().__init__(*a, **k)

    def score(self, x, tidx, snap=None):
        self.entered.set()
        self.gate.wait(10)
        return super().score(x, tidx, snap)


def _fairness_run(max_group_rows):
    """10 firehose requests (16 rows each, tenant 'hot') then one quiet
    4-row request; returns how many hot responses resolved BEFORE the
    quiet one."""
    ts = _random_tenant_set(2, seed=10, d=D)
    ts = ts._replace(ids=("hot", "quiet"),
                     versions=np.zeros(2, np.int64))
    scorer = GatedTenantScorer(ts)
    cfg = ServiceConfig(max_batch_rows=64, max_group_rows=max_group_rows)
    order = []
    with TenantScoringService(scorer, cfg) as svc:
        rng = np.random.default_rng(0)
        futs = []
        first = svc.submit("hot", rng.normal(size=(16, D)))
        first.add_done_callback(lambda _f: order.append("hot"))
        futs.append(first)
        time.sleep(0.2)             # the gated worker holds request #0
        for _ in range(9):
            f = svc.submit("hot", rng.normal(size=(16, D)))
            f.add_done_callback(lambda _f: order.append("hot"))
            futs.append(f)
        fq = svc.submit("quiet", rng.normal(size=(4, D)))
        fq.add_done_callback(lambda _f: order.append("quiet"))
        futs.append(fq)
        scorer.gate.set()
        for f in futs:
            f.result(30)
    return order.index("quiet")


def test_group_cap_prevents_starvation():
    # cap=16: dispatch 2 is [hot#1 (at cap), quiet] — the quiet tenant
    # rides the SECOND batch instead of waiting out the firehose.
    assert _fairness_run(16) <= 2
    # control: uncapped FIFO runs drain the whole firehose first
    assert _fairness_run(None) == 10


class _RecordingTenantService(TenantScoringService):
    """Records each packed batch's parts as ``(tenant, rows, version,
    tenant rows of its records)``."""

    def __init__(self, *a, **k):
        self.packed = []
        super().__init__(*a, **k)

    def _pack(self, scorer, reqs):
        batch = super()._pack(scorer, reqs)
        tidx = np.concatenate([tp[:n] for n, (_, tp) in batch.pieces])
        parts, off = [], 0
        for r, v in zip(reqs, batch.versions):
            parts.append((r.group, r.n, v, set(tidx[off:off + r.n])))
            off += r.n
        self.packed.append(parts)
        return batch


def test_split_across_tenants_keeps_rows_versions_and_the_cap():
    """Cross-tenant batches on a backed-up queue under a fairness cap:
    requests are split to fill batches, each part scores against its
    own tenant's row and reports that tenant's version, every answer
    equals the tenant's own assignment, and a split's first part counts
    against its tenant's cap: no tenant passes the cap in any batch."""
    ts = _random_tenant_set(4, seed=12, d=D)
    scorer = GatedTenantScorer(ts, replica="t0")
    cfg = ServiceConfig(max_batch_rows=64, bucket_base=16,
                        max_group_rows=24)
    svc = _RecordingTenantService(scorer, cfg)
    rng = np.random.default_rng(13)
    sent = [("u0", rng.normal(size=(64, D)))]
    futs = [svc.submit(*sent[0])]
    assert scorer.entered.wait(10)         # the worker holds the plug
    for _ in range(40):
        sent.append((f"u{rng.integers(0, 4)}",
                     rng.normal(size=(int(rng.integers(1, 25)), D))))
        futs.append(svc.submit(*sent[-1]))
    scorer.gate.set()
    results = [f.result(30) for f in futs]
    svc.close()
    for (tenant, x), res in zip(sent, results):
        want, version = scorer.assign(tenant, x)
        np.testing.assert_array_equal(res.assignments, want)
        assert res.version == version == int(ts.versions[ts.index(tenant)])
    for parts in svc.packed:
        per_tenant = {}
        for tenant, n, version, rows in parts:
            assert rows == {ts.index(tenant)}
            assert version == int(ts.versions[ts.index(tenant)])
            per_tenant[tenant] = per_tenant.get(tenant, 0) + n
        assert parts is svc.packed[0] or max(per_tenant.values()) <= 24
    assert obs.counter("serve.split", replica="t0").value > 0
    assert obs.counter("serve.served", replica="t0").value == len(sent)


def test_group_cap_requires_positive():
    with pytest.raises(ValueError):
        ServiceConfig(max_group_rows=0)


# -------------------------------------------------- chunk store stats --

def test_store_stats_one_pass_match_numpy():
    rng = np.random.default_rng(3)
    x = (rng.normal(size=(1000, 4)) * [1, 5, 0, 2]).astype(np.float32)
    store = ChunkStore.ingest([x[:300], x[300:]], chunk_rows=128)
    st = store.stats()
    assert st.count == 1000
    np.testing.assert_allclose(st.minimum, x.min(0), rtol=1e-6)
    np.testing.assert_allclose(st.maximum, x.max(0), rtol=1e-6)
    np.testing.assert_allclose(st.mean, x.astype(np.float64).mean(0),
                               rtol=1e-9, atol=1e-9)
    np.testing.assert_allclose(st.var, x.astype(np.float64).var(0),
                               rtol=1e-6, atol=1e-12)


def test_store_stats_persist_in_manifest():
    rng = np.random.default_rng(4)
    x = rng.uniform(-2, 7, size=(500, 3)).astype(np.float32)
    with tempfile.TemporaryDirectory() as d:
        ChunkStore.ingest(x, chunk_rows=64, cache_dir=d)
        st = ChunkStore.open(d).stats()     # no data re-scan: manifest
        np.testing.assert_allclose(st.mean, x.astype(np.float64).mean(0))
        assert st.count == 500


def test_store_normalizer_standard_and_minmax():
    rng = np.random.default_rng(5)
    x = np.concatenate([rng.normal(3.0, 2.0, size=(400, 2)),
                        np.full((400, 1), 6.0)], axis=1  # constant col
                       ).astype(np.float32)
    store = ChunkStore.ingest(x, chunk_rows=100)
    z = store.normalizer("standard")(x)
    np.testing.assert_allclose(z[:, :2].mean(0), 0.0, atol=1e-4)
    np.testing.assert_allclose(z[:, :2].std(0), 1.0, atol=1e-4)
    assert np.all(z[:, 2] == 0.0)           # constant col: scale floors
    u = store.normalizer("minmax")(x)
    assert u[:, :2].min() >= 0.0 and u[:, :2].max() <= 1.0 + 1e-6
    with pytest.raises(ValueError):
        store.normalizer("weird")


def test_store_stats_absent_on_legacy_manifest():
    import json
    import os
    rng = np.random.default_rng(6)
    with tempfile.TemporaryDirectory() as d:
        ChunkStore.ingest(rng.normal(size=(100, 2)).astype(np.float32),
                          chunk_rows=64, cache_dir=d)
        p = os.path.join(d, "manifest.json")
        with open(p) as f:
            man = json.load(f)
        del man["col_stats"]                # a pre-stats cache
        with open(p, "w") as f:
            json.dump(man, f)
        legacy = ChunkStore.open(d)         # still opens (additive key)
        assert legacy.stats() is None
        with pytest.raises(Exception):
            legacy.normalizer()


# ------------------------------------------------------------------ obs --

def test_tenant_fit_and_assign_spans_labeled():
    data = _cohort(5, seed=12)
    ts = fit_tenants(data, CFG)
    scorer = TenantScorer(ts)
    with TenantScoringService(scorer) as svc:
        svc.score("t0", data["t0"][:6], timeout=30)
    scorer.assign("t0", data["t0"][:6])
    hists = obs.metrics_snapshot()["histograms"]
    assert "span.tenant.fit{tenants=5}" in hists
    assert "span.tenant.fit" in hists       # unlabeled aggregate
    assert "span.tenant.assign{tenants=1}" in hists
    # the service's batches run the base service's worker loop
    assert "span.serve.assign{replica=t0}" in hists
