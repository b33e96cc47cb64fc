"""BigFCM (paper Algorithm 3) on a JAX device mesh.

Structure mirrors the paper exactly:

  Driver   — sample λ records (Parker–Hall), run plain FCM *and* WFCMPB on
             the sample, time both, keep the faster one's centers (Flag).
             The winning centers play the role of the Hadoop distributed
             cache file: they enter the SPMD program as a replicated array.
  Mapper   — host data pipeline hands each device its row-shard
             (`repro.data.loader`); record parsing is host-side.
  Combiner — inside `shard_map`: per-device (weighted) FCM to LOCAL
             convergence using the cached seeds.  No collectives inside the
             local loop, so shards may take different iteration counts —
             a slow shard only delays the final gather (the TPU analogue
             of Hadoop's combiner locality + speculative execution).
  Reducer  — `all_gather` of the (P·C centers, P·C weights) — a few KB —
             then one `engine.merge_summaries` flat plan over them.  With
             a pod axis, ``hierarchical=True`` merges within each pod
             first and then across pods (the paper's "multiple reduce
             jobs" variant) — the same plan at two gather levels.

The sweep implementation is a single config axis: ``cfg.backend`` names a
`repro.engine.SweepBackend` (``"auto"`` resolves per platform), resolved
once in `bigfcm_fit` and threaded to the driver, combiner, and reducer.
The combiner+reducer is ONE jit'd XLA program: the paper's "just one
map-reduce job works iteratively" claim.  The per-iteration-job baseline
(Ludwig / Mahout FKM) lives in `repro.baselines.mr_fkm`.

**Out-of-core** (the data side of the paper's caching design): passing a
`repro.data.cache.ChunkStore` instead of an array — or calling
`bigfcm_fit_store` directly — runs the same structure against a dataset
that never fits in memory.  Combiners consume chunk shards from a
deterministic `repro.data.plane.PartitionPlan`; each local fit is the
multi-pass `repro.core.outofcore.ooc_fcm` (every iteration streams the
shard's memory-mapped chunks through the engine's raw-accumulate entry,
summing partials across chunks before ONE normalization) when the
driver race picks FCM, or the single-pass `wfcmpb_store` progression
when it picks WFCMPB; the reducer is the identical flat merge plan over
the shard summaries.
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import shard_map
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import obs
from repro.data.cache import ChunkStore
from repro.data.plane import PartitionPlan, batched, pad_rows, \
    plan_partitions, shard_batches
from repro.engine import (MergePlan, Summary, merge_summaries,
                          resolve_backend)

from .fcm import fcm
from .outofcore import make_accumulator, ooc_accumulate, ooc_fcm
from .sampling import parker_hall_sample_size
from .wfcmpb import wfcmpb, wfcmpb_store


@dataclasses.dataclass(frozen=True)
class BigFCMConfig:
    n_clusters: int
    m: float = 2.0
    driver_eps: float = 5e-11      # Table 2: tight driver ε ⇒ 6× total win
    combiner_eps: float = 1e-8
    reducer_eps: float = 5e-11
    max_iter: int = 1000
    alpha: float = 0.05            # Parker–Hall confidence
    r: float = 0.10                # Parker–Hall relative class difference
    sample_size: Optional[int] = None   # override Eq. (4) if set
    block_size: int = 2048         # WFCMPB block size
    hierarchical: bool = False     # two-level reduce over ('data') then ('pod')
    backend: str = "auto"          # engine sweep backend (jnp/pallas/...)
    use_driver: bool = True        # False = random seeds (Table 2 baseline)
    seed: int = 0

    def reducer_plan(self) -> MergePlan:
        """The reducer's merge plan (paper line 13 seeds with V_1)."""
        return MergePlan("flat", seed="first", m=self.m,
                         eps=self.reducer_eps, max_iter=self.max_iter)


class BigFCMDiagnostics(NamedTuple):
    flag: bool                 # True ⇒ plain FCM won the driver race
    t_fcm_driver: float        # seconds — driver FCM on the sample
    t_wfcmpb_driver: float     # seconds — driver WFCMPB on the sample
    sample_size: int
    combiner_iters: jax.Array  # (P,) local iteration counts (straggler view)
    reducer_iters: jax.Array   # ()


class BigFCMResult(NamedTuple):
    centers: jax.Array         # (C, d) — V_final
    center_weights: jax.Array  # (C,)
    objective: jax.Array       # () global fuzzy objective vs. final centers
    diagnostics: BigFCMDiagnostics


# ---------------------------------------------------------------- driver ---

def run_driver(x_sample: jax.Array, cfg: BigFCMConfig, key: jax.Array):
    """Pre-cluster the sample; race FCM vs WFCMPB (paper lines 1–6)."""
    c = cfg.n_clusters
    idx = jax.random.choice(key, x_sample.shape[0], (c,), replace=False)
    seeds = jnp.take(x_sample, idx, axis=0)
    be = resolve_backend(cfg.backend,
                         shape=(x_sample.shape[0], c, x_sample.shape[1]))

    f_fcm = jax.jit(partial(fcm, m=cfg.m, eps=cfg.driver_eps,
                            max_iter=cfg.max_iter, backend=be))
    f_pb = jax.jit(partial(wfcmpb, m=cfg.m, eps=cfg.driver_eps,
                           max_iter=cfg.max_iter, block_size=cfg.block_size,
                           backend=be))
    # Warm up compilation outside the race (Hadoop's JVM is warm too).
    jax.block_until_ready(f_fcm(x_sample, seeds))
    jax.block_until_ready(f_pb(x_sample, seeds))

    t0 = time.perf_counter()
    res_fcm = jax.block_until_ready(f_fcm(x_sample, seeds))
    t_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    res_pb = jax.block_until_ready(f_pb(x_sample, seeds))
    t_f = time.perf_counter() - t0

    flag = t_f - t_s > 0         # paper line 6: Flag=1 ⇒ FCM to the cache
    obs.event("engine.driver_race", flag=bool(flag), t_fcm=t_s,
              t_wfcmpb=t_f, backend=be.name,
              sample_rows=int(x_sample.shape[0]))
    v_init = res_fcm.centers if flag else res_pb.centers
    return v_init, flag, t_s, t_f


def driver_seeds(store: ChunkStore, cfg: BigFCMConfig, *,
                 key: Optional[jax.Array] = None) -> np.ndarray:
    """Derive the driver's seed centers from a store with ZERO
    coordination — the fleet entry point.

    Every fleet host calls this independently and must land on
    bit-identical seeds, so the wall-clock FCM-vs-WFCMPB race of
    `run_driver` cannot apply: two hosts can legitimately time the race
    differently and diverge.  The race is pinned to Flag=1 (plain FCM
    pre-clustering, the paper's common case) — same sample
    (`store.take` of the same Parker–Hall indices), same seeds, same
    deterministic XLA program, so N hosts agree without exchanging a
    byte.  With ``cfg.use_driver=False`` this is the Table-2 random-seed
    ablation (equally deterministic).
    """
    key = key if key is not None else jax.random.PRNGKey(cfg.seed)
    k_sample, k_seed = jax.random.split(key)
    n = store.n_rows
    lam = cfg.sample_size or parker_hall_sample_size(
        cfg.n_clusters, cfg.r, cfg.alpha)
    lam = min(lam, n)
    x_sample = jnp.asarray(store.take(_sample_rows(k_sample, n, lam)))
    idx = jax.random.choice(k_seed, x_sample.shape[0], (cfg.n_clusters,),
                            replace=False)
    seeds = jnp.take(x_sample, idx, axis=0)
    if not cfg.use_driver:
        return np.asarray(seeds)
    be = resolve_backend(cfg.backend, shape=(x_sample.shape[0],
                                             cfg.n_clusters, store.dim))
    res = fcm(x_sample, seeds, m=cfg.m, eps=cfg.driver_eps,
              max_iter=cfg.max_iter, backend=be)
    return np.asarray(res.centers)


def _initial_centers(x_sample: jax.Array, cfg: BigFCMConfig, k_seed):
    """Driver race (lines 1–6), or the Table-2 random-seed ablation —
    shared by the in-memory and out-of-core fit paths."""
    if cfg.use_driver:
        return run_driver(x_sample, cfg, k_seed)
    idx = jax.random.choice(k_seed, x_sample.shape[0], (cfg.n_clusters,),
                            replace=False)
    return jnp.take(x_sample, idx, axis=0), True, 0.0, 0.0


# --------------------------------------------------- combiner + reducer ---

def _combine_reduce(x_local, w_local, v_init, *, cfg: BigFCMConfig,
                    flag: bool, backend, data_axes, pod_axis):
    """shard_map body: local clustering, then the gathered summary stack
    through the engine's flat merge plan (once, or per hierarchy level)."""
    if flag:
        local = fcm(x_local, v_init, m=cfg.m, eps=cfg.combiner_eps,
                    max_iter=cfg.max_iter, point_weights=w_local,
                    backend=backend)
    else:
        local = wfcmpb(x_local, v_init, m=cfg.m, eps=cfg.combiner_eps,
                       max_iter=cfg.max_iter, block_size=cfg.block_size,
                       point_weights=w_local, backend=backend)
    plan = cfg.reducer_plan()

    def gather_merge(summary: Summary, axes, init):
        gathered = Summary(jax.lax.all_gather(summary.centers, axes),
                           jax.lax.all_gather(summary.masses, axes))
        # ``init`` carries the hierarchy level's explicit seed; the flat
        # plan's seed="first" (V_1, paper line 13) applies when None.
        return merge_summaries(gathered, plan, backend=backend, init=init)

    local_sum = Summary(local.centers, local.center_weights)
    if cfg.hierarchical and pod_axis is not None:
        inner_axes = tuple(a for a in data_axes if a != pod_axis)
        mid = gather_merge(local_sum, inner_axes, local.centers)
        red = gather_merge(mid.summary, (pod_axis,), mid.summary.centers)
    else:
        red = gather_merge(local_sum, data_axes, None)

    # Global objective of the final centers over the full dataset —
    # the accumulate entry's q output (Σ w·u^m·d²), through the backend.
    centers = red.summary.centers
    _, _, q_local = backend.accumulate(x_local, w_local, centers, cfg.m)
    q = jax.lax.psum(q_local, data_axes)
    iters = jax.lax.all_gather(local.n_iter, data_axes)
    return centers, red.summary.masses, q, iters, red.n_iter


def mesh_job(mesh: Mesh, cfg: BigFCMConfig, *, flag: bool, backend,
             data_axes: Sequence[str] = ("data",)):
    """The combiner+reducer as ONE `shard_map` program over ``mesh``:
    ``(x, w, v_init)``, with x and w row-sharded over ``data_axes`` and
    v_init replicated → ``(centers, masses, q, combiner iters, reducer
    iters)``.  Rows must divide evenly over the data axes."""
    data_axes = tuple(data_axes)
    pod_axis = "pod" if "pod" in mesh.axis_names else None
    return shard_map(
        partial(_combine_reduce, cfg=cfg, flag=flag, backend=backend,
                data_axes=data_axes, pod_axis=pod_axis),
        mesh=mesh,
        in_specs=(P(data_axes), P(data_axes), P(None, None)),
        out_specs=(P(None, None), P(None), P(), P(None), P()),
        check_vma=False,
    )


# ------------------------------------------------------------------ fit ---

def bigfcm_fit(
    x: jax.Array,
    cfg: BigFCMConfig,
    *,
    mesh: Optional[Mesh] = None,
    data_axes: Sequence[str] = ("data",),
    point_weights: Optional[jax.Array] = None,
    key: Optional[jax.Array] = None,
) -> BigFCMResult:
    """Cluster ``x`` (N, d) with BigFCM on ``mesh`` (or single device).

    ``x`` may also be a `ChunkStore`, in which case the fit runs the
    out-of-core path (`bigfcm_fit_store`) — logical shard combiners
    streaming memory-mapped chunks, no mesh placement."""
    if isinstance(x, ChunkStore):
        if mesh is not None or point_weights is not None:
            raise ValueError(
                "bigfcm_fit over a ChunkStore is the out-of-core path: "
                "mesh/point_weights are not supported — materialize the "
                "store for the in-memory mesh path, or call "
                "bigfcm_fit_store for shard-planned control")
        return bigfcm_fit_store(x, cfg, key=key)
    # The whole in-memory fit is one `engine.fit` span (the out-of-core
    # delegation above gets its own `engine.fit_store` — never both).
    with obs.span("engine.fit", rows=int(x.shape[0])):
        return _fit_array(x, cfg, mesh=mesh, data_axes=data_axes,
                          point_weights=point_weights, key=key)


def _fit_array(x, cfg: BigFCMConfig, *, mesh, data_axes, point_weights,
               key) -> BigFCMResult:
    key = key if key is not None else jax.random.PRNGKey(cfg.seed)
    k_sample, k_seed = jax.random.split(key)
    n = x.shape[0]
    be = resolve_backend(cfg.backend,
                         shape=(n, cfg.n_clusters, x.shape[1]))

    lam = cfg.sample_size or parker_hall_sample_size(
        cfg.n_clusters, cfg.r, cfg.alpha)
    lam = min(lam, n)
    sample_idx = jax.random.choice(k_sample, n, (lam,), replace=False)
    x_sample = jnp.take(jnp.asarray(x), sample_idx, axis=0)

    v_init, flag, t_s, t_f = _initial_centers(x_sample, cfg, k_seed)

    w = (jnp.ones((n,), jnp.float32) if point_weights is None
         else jnp.asarray(point_weights, jnp.float32))

    if mesh is None or len(mesh.devices.flatten()) == 1:
        local = fcm(x, v_init, m=cfg.m, eps=cfg.combiner_eps,
                    max_iter=cfg.max_iter, point_weights=w, backend=be)
        # Degenerate reduce (one combiner summary): the reducer WFCM is
        # just a polish of the local sketch against itself.
        red = fcm(local.centers, local.centers, m=cfg.m,
                  eps=cfg.reducer_eps, max_iter=cfg.max_iter,
                  point_weights=local.center_weights, backend=be)
        diag = BigFCMDiagnostics(flag, t_s, t_f, lam,
                                 local.n_iter[None], red.n_iter)
        obs.event("engine.fit.done", backend=be.name, path="memory",
                  flag=bool(flag), objective=float(red.objective),
                  combiner_iters=int(local.n_iter),
                  reducer_iters=int(red.n_iter))
        return BigFCMResult(red.centers, red.center_weights, red.objective,
                            diag)

    data_axes = tuple(data_axes)
    # Rows shard evenly over the data axes: pad to a multiple of their
    # size with zero-weight phantom rows, a no-op in every accumulator.
    n_pad = -n % int(np.prod([mesh.shape[a] for a in data_axes]))
    if n_pad:
        x = pad_rows(np.asarray(x), n + n_pad)
        w = jnp.concatenate([w, jnp.zeros((n_pad,), jnp.float32)])
    job = mesh_job(mesh, cfg, flag=flag, backend=be, data_axes=data_axes)
    x_sharded = jax.device_put(x, NamedSharding(mesh, P(data_axes)))
    w_sharded = jax.device_put(w, NamedSharding(mesh, P(data_axes)))
    v_rep = jax.device_put(v_init, NamedSharding(mesh, P(None, None)))
    centers, cw, q, iters, r_it = jax.jit(job)(x_sharded, w_sharded, v_rep)
    obs.event("engine.fit.done", backend=be.name, path="mesh",
              flag=bool(flag), objective=float(q),
              reducer_iters=int(r_it))
    diag = BigFCMDiagnostics(flag, t_s, t_f, lam, iters, r_it)
    return BigFCMResult(centers, cw, q, diag)


# ------------------------------------------------------- out-of-core fit ---

# Above this many rows the driver sample is drawn host-side in O(λ)
# memory; `jax.random.choice(..., replace=False)` materializes O(n)
# keys on device, which would defeat the out-of-core contract.
_DEVICE_SAMPLE_ROWS = 1 << 24


def _sample_rows(k_sample, n: int, lam: int) -> np.ndarray:
    """λ distinct row indices from [0, n).  Device path below the size
    cutoff (bit-identical to the in-memory fit's sample); O(λ)-memory
    host-side rejection sampling above it (λ ≪ n there, so collisions
    are negligible)."""
    if n <= _DEVICE_SAMPLE_ROWS:
        return np.asarray(jax.random.choice(k_sample, n, (lam,),
                                            replace=False))
    rng = np.random.default_rng(
        int(jax.random.randint(k_sample, (), 0, np.iinfo(np.int32).max)))
    seen: dict = dict.fromkeys(rng.integers(0, n, lam, dtype=np.int64))
    while len(seen) < lam:
        seen.update(dict.fromkeys(
            rng.integers(0, n, lam - len(seen), dtype=np.int64)))
    return np.fromiter(seen, np.int64, count=lam)


def bigfcm_fit_store(
    store: ChunkStore,
    cfg: BigFCMConfig,
    *,
    n_shards: int = 1,
    plan: Optional[PartitionPlan] = None,
    batch_rows: Optional[int] = None,
    key: Optional[jax.Array] = None,
) -> BigFCMResult:
    """BigFCM over a `ChunkStore` that need not fit in memory.

    The paper's structure, host-orchestrated over the chunk cache:

      Driver   — Parker–Hall sample gathered by global row index
                 (`store.take`), same race / same seeds as the
                 in-memory path.
      Combiner — one per `PartitionPlan` shard (default: one shard =
                 the whole store).  Multi-pass `ooc_fcm` when the race
                 picks FCM — every iteration streams the shard's
                 chunks through the backend's raw-accumulate entry and
                 normalizes once — or single-pass `wfcmpb_store` when
                 it picks WFCMPB.
      Reducer  — the identical flat merge plan over the gathered shard
                 summaries (degenerate self-polish for one shard), then
                 one chunk pass for the global objective.

    ``batch_rows`` (default: the store's chunk size) is the device
    working-set: peak device memory is O(batch_rows·d + C·d) however
    large the store is.  One shard mirrors the in-memory single-device
    branch exactly — multi-pass FCM combiner *regardless of flag* (that
    branch ignores the race too) plus the same degenerate self-polish —
    so a store that *does* fit reproduces `bigfcm_fit` on the
    materialized array to float32 summation order; the WFCMPB combiner
    applies on multi-shard plans, mirroring the mesh combiners.
    """
    with obs.span("engine.fit_store", rows=int(store.n_rows)):
        return _fit_store(store, cfg, n_shards=n_shards, plan=plan,
                          batch_rows=batch_rows, key=key)


def _fit_store(store: ChunkStore, cfg: BigFCMConfig, *, n_shards, plan,
               batch_rows, key) -> BigFCMResult:
    key = key if key is not None else jax.random.PRNGKey(cfg.seed)
    k_sample, k_seed = jax.random.split(key)
    n = store.n_rows
    be = resolve_backend(cfg.backend,
                         shape=(n, cfg.n_clusters, store.dim))

    lam = cfg.sample_size or parker_hall_sample_size(
        cfg.n_clusters, cfg.r, cfg.alpha)
    lam = min(lam, n)
    x_sample = jnp.asarray(store.take(_sample_rows(k_sample, n, lam)))

    v_init, flag, t_s, t_f = _initial_centers(x_sample, cfg, k_seed)

    if plan is None:
        # more shards than chunks would leave empty combiners — clamp
        plan = plan_partitions(store, min(n_shards, store.n_chunks))
    rows = int(batch_rows or store.chunk_rows)
    shards = [s for s in range(plan.n_shards) if plan.shard_rows[s] > 0]
    if not shards:
        raise ValueError("bigfcm_fit_store: partition plan has no "
                         "non-empty shard")
    acc = make_accumulator(be, cfg.m)  # ONE compile for every shard/pass
    locals_ = []
    for s in shards:                   # empty shards contribute nothing
        with obs.span("engine.combiner", shard=s):
            if flag or len(shards) == 1:  # 1 shard ≡ single-device branch
                loc = ooc_fcm(
                    lambda s=s: shard_batches(store, plan, s, rows),
                    v_init, m=cfg.m, eps=cfg.combiner_eps,
                    max_iter=cfg.max_iter, backend=be, acc=acc)
            else:
                loc = wfcmpb_store(store, v_init, m=cfg.m,
                                   eps=cfg.combiner_eps,
                                   max_iter=cfg.max_iter, batch_rows=rows,
                                   backend=be, plan=plan, shard=s,
                                   with_objective=False)
        locals_.append(loc)
    iters = jnp.stack([loc.n_iter for loc in locals_])

    if len(locals_) == 1:
        # Degenerate reduce (one combiner summary): the reducer WFCM is
        # just a polish of the local sketch against itself — identical
        # to the in-memory single-device branch.
        local = locals_[0]
        with obs.span("engine.merge", shards=1):
            red = fcm(local.centers, local.centers, m=cfg.m,
                      eps=cfg.reducer_eps, max_iter=cfg.max_iter,
                      point_weights=local.center_weights, backend=be)
        obs.event("engine.fit.done", backend=be.name, path="store",
                  flag=bool(flag), objective=float(red.objective),
                  reducer_iters=int(red.n_iter))
        diag = BigFCMDiagnostics(flag, t_s, t_f, lam, iters, red.n_iter)
        return BigFCMResult(red.centers, red.center_weights, red.objective,
                            diag)

    stacked = Summary(jnp.stack([loc.centers for loc in locals_]),
                      jnp.stack([loc.center_weights for loc in locals_]))
    with obs.span("engine.merge", shards=len(locals_)):
        red = merge_summaries(stacked, cfg.reducer_plan(), backend=be)
    # Global objective of the merged centers over the full store — one
    # more chunk pass through the raw accumulate entry (the q output).
    _, _, q = ooc_accumulate(batched(store.iter_chunks(), rows),
                             red.summary.centers, cfg.m, acc=acc)
    obs.event("engine.fit.done", backend=be.name, path="store",
              flag=bool(flag), objective=float(q),
              reducer_iters=int(red.n_iter))
    diag = BigFCMDiagnostics(flag, t_s, t_f, lam, iters, red.n_iter)
    return BigFCMResult(red.summary.centers, red.summary.masses, q, diag)
