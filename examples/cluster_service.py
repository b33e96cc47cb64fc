"""Streaming clustering service: BigFCM over a sharded, prefetching data
pipeline with checkpoint/restart and straggler monitoring — the paper's
deployment story (multi-gigabyte HDFS scan) as a long-running service.

Data arrives in chunks (the HDFS-split analogue), each macro-batch is
clustered starting from the previous centers (warm start = the paper's
distributed-cache mechanism applied over *time* as well as space), and
the running (centers, weights) pair is itself WFCM-merged — the same
weighted-combine math that merges combiner outputs merges epochs.

    PYTHONPATH=src python examples/cluster_service.py
"""
import tempfile

import numpy as np

from repro.core.bigfcm import BigFCMConfig, bigfcm_fit
from repro.core.metrics import assign, clustering_accuracy, match_centers
from repro.engine import MergePlan, Summary, merge_summaries
from repro.data.loader import ShardedLoader, normalize
from repro.data.synth import make_kdd_like
from repro.ft.checkpoint import CheckpointManager
from repro.ft.elastic import StragglerMonitor
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh

enable_compile_cache()

C = 23                    # KDD99-like: 23 classes, 41 features
CHUNK, BATCH_ROWS, N_CHUNKS = 40_000, 120_000, 6

mesh = make_host_mesh()
ckpt = CheckpointManager(tempfile.mkdtemp(prefix="repro_fcm_ckpt_"))
monitor = StragglerMonitor(on_straggler=lambda dt, ew: print(
    f"  [straggler] step {dt:.2f}s vs EWMA {ew:.2f}s"))

# one big dataset, streamed in HDFS-split-sized chunks
x_all, _ = make_kdd_like(CHUNK * N_CHUNKS, seed=7)
stream = (x_all[i * CHUNK:(i + 1) * CHUNK] for i in range(N_CHUNKS))
loader = ShardedLoader(stream, BATCH_ROWS, mesh=mesh, transform=normalize)

cfg = BigFCMConfig(n_clusters=C, m=1.2, combiner_eps=1e-7,
                   reducer_eps=5e-11, max_iter=300)

epoch_plan = MergePlan("flat", m=cfg.m, eps=cfg.reducer_eps,
                       max_iter=cfg.max_iter)
centers, weights = None, None
for i, (batch, w) in enumerate(loader):
    monitor.start()
    res = bigfcm_fit(batch, cfg, mesh=mesh, point_weights=w)
    if centers is None:
        centers, weights = res.centers, res.center_weights
    else:  # the same engine merge that combines combiners merges epochs
        merged = merge_summaries(
            [Summary(centers, weights),
             Summary(res.centers, res.center_weights)],
            epoch_plan, init=centers)
        centers, weights = merged.summary.centers, merged.summary.masses
    monitor.stop()
    ckpt.save(i, {"centers": centers, "weights": weights})
    print(f"macro-batch {i}: objective {float(res.objective):.1f}, "
          f"combiner iters "
          f"{np.asarray(res.diagnostics.combiner_iters).ravel().tolist()}")

ckpt.wait()
print(f"\ncheckpoints kept: {ckpt.all_steps()} (atomic, keep-last-3)")

# quality check on a fresh sample from the same mixture (same seed ⇒
# same component centers, freshly drawn noise/labels)
x, y = make_kdd_like(60_000, seed=7)
x = normalize(x)
acc = clustering_accuracy(y, assign(x, np.asarray(centers)), C)
true_centers = np.stack([x[y == c].mean(0) for c in range(C)
                         if (y == c).any()])
err = match_centers(np.asarray(centers)[:len(true_centers)], true_centers)
print(f"held-out confusion accuracy: {acc:.3f}  center error: {err:.4f}")

# restart path: restore from latest checkpoint and keep serving
restored = ckpt.restore({"centers": centers, "weights": weights})
assert np.allclose(np.asarray(restored["centers"]),
                   np.asarray(centers), atol=1e-6)
print("OK -- restart restores the clustering state bit-exactly.")

# the first pass parsed the stream ONCE into the loader's chunk cache
# (the paper's node-local cache); nightly re-fits and archive scoring
# read the cache, never the stream.  One out-of-core refit over the
# whole history + chunk-by-chunk scoring of the archive:
from repro.core import bigfcm_fit_store          # noqa: E402
from repro.serve import assign_store             # noqa: E402

import dataclasses                               # noqa: E402

store = loader.store
print(f"\nchunk cache after ingest: {store!r}")
nightly = dataclasses.replace(cfg, use_driver=False, max_iter=60,
                              combiner_eps=1e-6)
refit = bigfcm_fit_store(store, nightly, n_shards=2)
labels = np.concatenate(list(assign_store(store, refit.centers)))
assert labels.shape[0] == store.n_rows
counts = np.bincount(labels, minlength=C)
print(f"out-of-core refit over {store.n_rows} cached rows "
      f"(objective {float(refit.objective):.1f}); archive scored "
      f"chunk-by-chunk, {int((counts > 0).sum())}/{C} clusters occupied.")

# ---------------------------------------------------------------------
# Everything above was instrumented as it ran: each fit, chunk read,
# checkpoint save, and per-chunk scoring call fed the `repro.obs`
# metrics/tracing plane (always on by default; REPRO_OBS=0 turns every
# instrumentation call into a no-op).  The report below is the
# Bendechache-style per-phase breakdown — where the wall time went
# (parse vs sweep vs merge vs checkpoint vs scoring), with p50/p99 per
# phase derived from log-bucket histograms, plus the cache counters
# (cold-parse vs warm-mmap bytes show the parse-once story as numbers).
#
# Set REPRO_OBS_DIR=/some/dir to ALSO flush these events to
# <dir>/events.jsonl at exit, then render a finished run post-mortem:
#     python -m repro.obs.report --jsonl /some/dir/events.jsonl
from repro import obs                            # noqa: E402

print("\n=== observability report (repro.obs) ===")
print(obs.render_report(top_events=3))
p99 = obs.histogram("span.serve.assign").quantile(0.99)
print(f"\nserve.assign p99 latency: {p99 * 1e3:.2f} ms "
      "(what the serving plane reads for its SLO)")

# ---------------------------------------------------------------------
# Part 3 — the serving plane (PR 8): one ONLINE learner, two read-only
# scorer replicas following its snapshots, and a coalescing front-end
# absorbing many concurrent clients.  The learner keeps ingesting while
# clients score: every ingest publishes a fresh (version, centers,
# weights) snapshot that hot-swaps into both replicas WITHOUT dropping
# or blocking the in-flight requests — each response still reports the
# single snapshot version it was scored against.
import collections                               # noqa: E402
import threading                                 # noqa: E402

from repro.serve import (CenterSnapshot, Scorer,  # noqa: E402
                         ScoringService, ServiceConfig, SnapshotPublisher)
from repro.stream import StreamConfig, StreamingBigFCM  # noqa: E402

print("\n=== serving plane: learner + 2 replicas + 8 clients ===")
obs.reset_metrics()
learner = StreamingBigFCM(StreamConfig(n_clusters=C, m=1.2, window=4,
                                       max_iter=60))
learner.ingest(normalize(x_all[:CHUNK]))          # seed centers
replicas = [Scorer(CenterSnapshot(0, learner.state.centers), m=1.2,
                   replica=f"r{i}") for i in range(2)]
pub = SnapshotPublisher(replicas)
learner.add_snapshot_listener(pub.publish)        # learn → swap, forever

svc = ScoringService(replicas, ServiceConfig(max_batch_rows=8192,
                                             bucket_base=256))
versions = []


def client(i):
    rng = np.random.default_rng(300 + i)
    for _ in range(12):
        n = int(rng.integers(200, 3000))
        at = int(rng.integers(0, len(x_all) - n))
        res = svc.score(normalize(x_all[at:at + n]), timeout=60)
        versions.append(res.version)


clients = [threading.Thread(target=client, args=(i,)) for i in range(8)]
for t in clients:
    t.start()
# the learner keeps learning DURING the client traffic: each ingest
# publishes a snapshot that hot-swaps both replicas mid-flight
for j in range(1, 4):
    learner.ingest(normalize(x_all[j * CHUNK:(j + 1) * CHUNK]))
for t in clients:
    t.join()
svc.close()

snap = obs.metrics_snapshot()
p99_srv = snap["histograms"]["span.serve.assign"]["p99"]
served = {k: v for k, v in snap["counters"].items()
          if k.startswith("serve.served")}
print(f"responses by snapshot version: "
      f"{dict(sorted(collections.Counter(versions).items()))}"
      f"  (learner published version {pub.latest().version} last)")
print(f"served per replica: {served}")
print(f"serve.assign p99 under 8-client load: {p99_srv * 1e3:.2f} ms "
      f"-- {len(versions)} responses, 0 dropped, hot-swapped mid-traffic")

# ---------------------------------------------------------------------
# Part 4 — the tenant plane (PR 10): the OTHER production shape.  Parts
# 1–3 fit one big model; "millions of users" deployments fit millions
# of SMALL ones — a per-user/per-cohort model over a few dozen rows
# each.  `fit_tenants` packs a whole cohort into one phantom-padded
# (T, n, d) block and converges every tenant inside ONE compiled
# launch (per-tenant done-mask; 1 device dispatch instead of 1000);
# `TenantScoringService` then routes requests by tenant id and
# coalesces cross-tenant traffic back into single gather-scored
# launches.  The stacked TenantSet checkpoints through the same
# CheckpointManager as Part 1 — one manifest for any T.
from repro.serve import TenantScorer, TenantScoringService  # noqa: E402
from repro.tenant import (TenantFitConfig, fit_tenants,  # noqa: E402
                          load_tenants, save_tenants)

N_TENANTS = 1000
print(f"\n=== tenant plane: {N_TENANTS} per-cohort models, one launch ===")
obs.reset_metrics()
rng = np.random.default_rng(42)
cohorts = {f"user{i}": (rng.normal(size=(int(rng.integers(8, 30)), 4))
                        + 3.0 * (i % 5)).astype(np.float32)
           for i in range(N_TENANTS)}
ts = fit_tenants(cohorts, TenantFitConfig(n_clusters=3, seed=0,
                                          eps=1e-4, max_iter=50,
                                          row_base=16, backend="jnp"))
launches = obs.metrics_snapshot()["counters"]["tenant.fit.launches"]
print(f"fit {ts.n_tenants} tenants ({sum(x.shape[0] for x in cohorts.values())}"
      f" records) in {int(launches)} device launch; median per-tenant "
      f"iters {int(np.median(ts.n_iter))}")

# stacked checkpoint: ONE manifest holds the whole fleet; restore a
# subset without touching the rest
save_tenants(ckpt, step=100, ts=ts)
two = load_tenants(ckpt, step=100, tenants=["user17", "user910"])
assert np.array_equal(two.centers[0], ts.centers[ts.index("user17")])
print(f"checkpointed all {ts.n_tenants}; restored subset {two.ids}")

# tenant-routed scoring: requests name a tenant, the front-end
# coalesces across tenants into one gather-scored launch per bucket
tsvc = TenantScoringService(TenantScorer(ts, replica="t0"),
                            ServiceConfig(max_batch_rows=4096,
                                          bucket_base=64,
                                          max_group_rows=512))
hits = []
for i in (3, 17, 401, 910):
    res = tsvc.score(f"user{i}", cohorts[f"user{i}"], timeout=60)
    hits.append((f"user{i}", int(res.assignments.shape[0]),
                 res.version))
tsvc.close()
print(f"routed scoring (tenant, rows, snapshot version): {hits}")
print("tenant plane: 1000 models fit/served/checkpointed as one batch")
