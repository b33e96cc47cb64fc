"""StreamingBigFCM — the paper's one-job map-reduce generalized to time.

The batch algorithm's shape (combiners converge locally, a weighted-FCM
reducer merges a few KB of summaries) is already an online primitive;
this module turns it into a state machine over an unbounded stream:

  ingest(batch):
    1. **event-time gate** (``cfg.event_time``) — records carry event
       times; a watermark trails the max event time seen by
       ``allowed_lateness``.  Records behind the watermark are dropped
       and counted (``late_dropped``); the survivors' summary is routed
       to the ring slot of its event-time *bucket* (`window.assign_slot`)
       instead of the arrival cursor, where it *merges into* any summary
       already holding the bucket through the engine's raw accumulate
       entry — a late summary, scaled by the decay it missed, lands
       exactly as if it had arrived on time.
    2. **drift probe** — fuzzy objective of the current global centers on
       the incoming batch, per unit mass, plus the per-record residual
       (min squared distance) profile (`drift.DriftDetector`).  Regime
       change now has two responses:
         * **partial** (a bounded outlier mass fraction): *cluster
           birth* — spawn one new center from the batch's
           highest-residual records (``birth_residual_quantile``) and
           let the combiner refine it; no state is forgotten.
         * **global** (objective drift with most of the batch outlying):
           the full fallback — re-run the paper's *driver* (FCM vs
           WFCMPB race on a fresh sample, `core.bigfcm.run_driver`) to
           re-seed and zero the window.
       Symmetrically, a center whose merged window mass decays below
       ``death_mass_floor`` × the mean center mass is retired (*cluster
       death*) once it has had a full window to accumulate.
    3. **combiner** — per-batch (weighted) FCM from the current centers;
       on a device mesh each shard converges locally inside `shard_map`
       and an in-program `engine.merge_summaries` flat plan merges the
       per-device summaries (the paper's reducer = hierarchy level 1:
       across devices).
    4. **window** — the batch summary lands in a decayed sliding window
       (arrival cursor or event-time bucket) and the window collapses
       through the merge plan named by ``cfg.merge_plan`` (hierarchy
       level 2: across time).  The default ``windowed`` plan fuses the
       old pairwise tree's log₂ W WFCM rounds into ONE WFCM whose every
       iteration accumulates raw per-slot sums via the backend's
       accumulate entry point (`fcm_accumulate_pallas` on the Pallas
       backends) and normalizes once.

The sweep implementation everywhere is ``cfg.backend`` — one engine
config axis shared with batch BigFCM.  State is a flat pytree of small
arrays (`StreamState`) so `ft.checkpoint.CheckpointManager` persists a
live stream with the same atomic/async machinery as training jobs;
birth/death change the center-axis length, which the self-describing
checkpoint manifest round-trips as-is.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Iterable, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import shard_map
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import obs
from repro.core.bigfcm import BigFCMConfig, run_driver
from repro.core.fcm import fcm
from repro.core.metrics import fuzzy_objective
from repro.engine import MergePlan, Summary, merge_summaries, resolve_backend
from repro.engine.backend import pairwise_sqdist
from .drift import DriftConfig, DriftDetector
from .window import (advance_window, assign_slot, init_slot_buckets,
                     init_window, place_summary, push_summary, window_mass,
                     window_summary)


@dataclasses.dataclass(frozen=True)
class StreamConfig:
    n_clusters: int
    m: float = 2.0
    combiner_eps: float = 1e-8
    reducer_eps: float = 5e-11
    max_iter: int = 300
    merge_max_iter: int = 200
    window: int = 8                  # sliding-window slots (mini-batches)
    decay: float = 0.9               # per-push exponential forgetting
    merge_plan: str = "windowed"     # window topology: windowed|pairwise|flat
    combiner_mode: str = "converge"  # "converge" | "sweep" (one-pass)
    backend: str = "auto"            # engine sweep backend (jnp/pallas/...)
    driver_sample: int = 512         # sample size for (re)seed driver race
    drift: DriftConfig = DriftConfig()
    reseed_cooldown: int = 3         # min batches between structural events
    event_time: bool = False         # bucket slots by event time, not arrival
    slot_span: float = 1.0           # event-time units per window bucket
    allowed_lateness: float = 0.0    # watermark lag behind max event time
    birth_residual_quantile: float = 0.95  # residual quantile seeding a birth
    death_mass_floor: float = 0.0    # retire center below floor×mean mass (0=off)
    max_centers: Optional[int] = None  # birth capacity cap (None: 2×n_clusters)
    seed: int = 0

    def __post_init__(self):
        if self.event_time:
            if self.slot_span <= 0:
                raise ValueError("event_time needs slot_span > 0")
            if self.allowed_lateness < 0:
                raise ValueError("allowed_lateness must be >= 0")
            if self.allowed_lateness > (self.window - 1) * self.slot_span:
                raise ValueError(
                    f"allowed_lateness {self.allowed_lateness} exceeds the "
                    f"ring span ({self.window - 1} x slot_span "
                    f"{self.slot_span}): a slot that old has been recycled; "
                    f"grow `window` or shrink `allowed_lateness`")

    def window_plan(self) -> MergePlan:
        return MergePlan(self.merge_plan, m=self.m, eps=self.reducer_eps,
                         max_iter=self.merge_max_iter)

    def slot_plan(self) -> MergePlan:
        """Late/same-bucket slot merges always go through the engine's
        raw accumulate entry (the ``windowed`` topology)."""
        return MergePlan("windowed", m=self.m, eps=self.reducer_eps,
                         max_iter=self.merge_max_iter)

    def center_cap(self) -> int:
        return (2 * self.n_clusters if self.max_centers is None
                else self.max_centers)


class StreamState(NamedTuple):
    """Checkpointable pytree — everything a restart needs."""
    centers: jax.Array        # (C, d) global windowed centers
    weights: jax.Array        # (C,)  their decayed masses
    win_centers: jax.Array    # (W, C, d) ring buffer of batch summaries
    win_weights: jax.Array    # (W, C)
    cursor: jax.Array         # () i32 next window slot (processing time)
    step: jax.Array           # () i32 batches ingested
    since_reseed: jax.Array   # () i32 batches since last structural event
    reseeds: jax.Array        # () i32 driver re-seed count
    key: jax.Array            # PRNG key for sampling/seeding
    slot_buckets: jax.Array   # (W,) i32 event-time bucket held by each slot
    ages: jax.Array           # (C,) i32 batches since each center was born
    max_event: jax.Array      # () f32 max event time seen (watermark anchor)
    late_dropped: jax.Array   # () i32 records dropped behind the watermark
    births: jax.Array         # () i32 centers spawned from residual mass
    deaths: jax.Array         # () i32 centers retired below the mass floor


class IngestReport(NamedTuple):
    step: int
    drifted: bool
    reseeded: bool
    reason: str               # "" | "objective" | "shift"
    objective_pre: float      # stale-center objective per unit mass
    objective_post: float     # merged-center objective per unit mass
    shift: float              # max per-center L2 move of the global model
    combiner_iters: np.ndarray
    mass: float               # decayed record mass in the window
    watermark: float = float("-inf")  # event-time watermark (−inf: no event time)
    late_dropped: int = 0     # records of THIS batch dropped as too late
    born: int = 0             # centers spawned this batch
    died: int = 0             # centers retired this batch
    n_centers: int = 0        # live center count after this batch


def _q_norm(x, w, centers, *, m):
    """Fuzzy objective per unit record mass (the drift statistic)."""
    q = fuzzy_objective(x, centers, m, point_weights=w)
    return q / jnp.maximum(jnp.sum(w), 1e-12)


def _residuals(x, centers):
    """Per-record min squared distance to the centers — the soft-assign
    residual profile the birth rule reads."""
    return jnp.min(pairwise_sqdist(x, centers), axis=-1)


def _combine_local(x, w, centers, *, cfg: StreamConfig, be):
    """One batch summary: local FCM to convergence, or a single
    accumulate sweep (``combiner_mode="sweep"`` — the cheapest online
    mode, one pass per batch)."""
    if cfg.combiner_mode == "sweep":
        v, wi, _ = be.sweep(x, w, centers, cfg.m)
        return v, wi, jnp.int32(1)
    res = fcm(x, centers, m=cfg.m, eps=cfg.combiner_eps,
              max_iter=cfg.max_iter, point_weights=w, backend=be)
    return res.centers, res.center_weights, res.n_iter


def _combine_mesh_body(x_l, w_l, v, *, cfg: StreamConfig, be, data_axes):
    """shard_map body: per-device combiner + in-program device reduce
    (the engine's flat plan over the gathered per-device summaries)."""
    c_l, w_l_c, it = _combine_local(x_l, w_l, v, cfg=cfg, be=be)
    gathered = Summary(jax.lax.all_gather(c_l, data_axes),
                       jax.lax.all_gather(w_l_c, data_axes))
    plan = MergePlan("flat", m=cfg.m, eps=cfg.reducer_eps,
                     max_iter=cfg.merge_max_iter)
    red = merge_summaries(gathered, plan, backend=be, init=v)
    its = jax.lax.all_gather(it, data_axes)
    return red.summary.centers, red.summary.masses, its


class StreamingBigFCM:
    """Online/windowed BigFCM over an unbounded chunk stream."""

    def __init__(self, cfg: StreamConfig, *, mesh=None,
                 data_axes: Sequence[str] = ("data",)):
        self.cfg = cfg
        self.mesh = mesh
        self.data_axes = tuple(data_axes)
        self.state: Optional[StreamState] = None
        self.detector = DriftDetector(cfg.drift)
        self._snapshot_listeners: list = []
        self.backend = resolve_backend(cfg.backend)
        be = self.backend
        # Driver config for (re)seeding: the paper's FCM-vs-WFCMPB race.
        self._bcfg = BigFCMConfig(
            n_clusters=cfg.n_clusters, m=cfg.m, driver_eps=cfg.reducer_eps,
            combiner_eps=cfg.combiner_eps, reducer_eps=cfg.reducer_eps,
            max_iter=cfg.max_iter, sample_size=cfg.driver_sample,
            backend=cfg.backend, seed=cfg.seed)
        self._jq = jax.jit(partial(_q_norm, m=cfg.m))
        self._jresid = jax.jit(_residuals)
        if mesh is None:
            self._jcomb = jax.jit(
                partial(_combine_local, cfg=cfg, be=be))
        else:
            self._jcomb = jax.jit(shard_map(
                partial(_combine_mesh_body, cfg=cfg, be=be,
                        data_axes=self.data_axes),
                mesh=mesh,
                in_specs=(P(self.data_axes), P(self.data_axes), P(None, None)),
                out_specs=(P(None, None), P(None), P(None)),
                check_vma=False))
        plan = cfg.window_plan()

        def _window_merge(win_c, win_w):
            res = merge_summaries(window_summary(win_c, win_w), plan,
                                  backend=be)
            return res.summary.centers, res.summary.masses

        self._jmerge = jax.jit(_window_merge)

    # ------------------------------------------------------------- seed --
    def _driver_seed(self, x: jax.Array, w: jax.Array,
                     key: jax.Array) -> jax.Array:
        """Run the paper's driver race on a sample of ``x`` → C seeds.

        Sampling is mass-weighted so zero-weight phantom rows (loader
        tail padding) can never become seeds — the sample size is capped
        by the number of real rows because ``choice(replace=False)``
        falls back to zero-probability rows once the weighted ones are
        exhausted."""
        k_sample, k_seed = jax.random.split(key)
        n = x.shape[0]
        n_real = int(jnp.sum(w > 0))
        if n_real == 0:
            raise ValueError("cannot seed StreamingBigFCM from a "
                             "zero-mass (all-phantom) batch")
        lam = min(self.cfg.driver_sample, n_real)
        p = w / jnp.maximum(jnp.sum(w), 1e-12)
        idx = np.asarray(jax.random.choice(k_sample, n, (lam,),
                                           replace=False, p=p))
        # The sample is gathered on the host: on a mesh ``x`` is
        # row-sharded, and a device gather of sharded rows has no
        # unambiguous output sharding under `Explicit` mesh axes.
        v, _flag, _ts, _tf = run_driver(jnp.asarray(np.asarray(x)[idx]),
                                        self._bcfg, k_seed)
        return v

    def _fresh_state(self, x: jax.Array, w: jax.Array, key: jax.Array,
                     reseeds: int, step: int,
                     carry: Optional[StreamState] = None) -> StreamState:
        """(Re)seeded state.  ``carry`` preserves the monotone stream
        metrics (event clock, late/birth/death counters) across a
        re-seed — the stale regime's *window* is forgotten, time is not.
        """
        centers = self._driver_seed(x, w, key)
        c, d = centers.shape
        win_c, win_w = init_window(self.cfg.window, c, d)
        return StreamState(
            centers=centers, weights=jnp.zeros((c,), jnp.float32),
            win_centers=win_c, win_weights=win_w,
            cursor=jnp.int32(0), step=jnp.int32(step),
            since_reseed=jnp.int32(0), reseeds=jnp.int32(reseeds),
            key=jax.random.fold_in(key, reseeds + 1),
            slot_buckets=init_slot_buckets(self.cfg.window),
            ages=jnp.zeros((c,), jnp.int32),
            max_event=(jnp.float32(-jnp.inf) if carry is None
                       else carry.max_event),
            late_dropped=(jnp.int32(0) if carry is None
                          else carry.late_dropped),
            births=jnp.int32(0) if carry is None else carry.births,
            deaths=jnp.int32(0) if carry is None else carry.deaths)

    # ------------------------------------------------------ birth/death --
    def _spawn_center(self, st: StreamState, x, w, resid: np.ndarray
                      ) -> StreamState:
        """Cluster birth: one new center at the weighted centroid of the
        batch's highest-residual records (above
        ``birth_residual_quantile``); its window rows start phantom and
        fill as batches arrive."""
        w_np = np.asarray(w)
        real = w_np > 0
        k = float(np.quantile(resid[real], self.cfg.birth_residual_quantile))
        cand = (resid >= k) & real
        new_c = np.average(np.asarray(x)[cand], axis=0,
                           weights=w_np[cand]).astype(np.float32)
        wnd = st.win_centers.shape[0]
        d = st.centers.shape[1]
        pad_c = jnp.broadcast_to(jnp.asarray(new_c)[None, None, :],
                                 (wnd, 1, d))
        return st._replace(
            centers=jnp.concatenate([st.centers,
                                     jnp.asarray(new_c)[None, :]], axis=0),
            weights=jnp.concatenate([st.weights,
                                     jnp.zeros((1,), jnp.float32)]),
            win_centers=jnp.concatenate([st.win_centers, pad_c], axis=1),
            win_weights=jnp.concatenate(
                [st.win_weights, jnp.zeros((wnd, 1), jnp.float32)], axis=1),
            ages=jnp.concatenate([st.ages, jnp.zeros((1,), jnp.int32)]),
            births=st.births + 1)

    # ------------------------------------------------------- event time --
    def _event_place(self, st_in: StreamState, sc, sw, t_batch: float,
                     wm: float, new_max: float):
        """Route one batch summary to its event-time slot.  Returns
        (win_c, win_w, slot_buckets, placed)."""
        cfg = self.cfg
        bucket, slot, late = assign_slot(t_batch, wm,
                                         slot_span=cfg.slot_span,
                                         window=cfg.window)
        win_c, win_w, sb = (st_in.win_centers, st_in.win_weights,
                            st_in.slot_buckets)
        old_max = float(st_in.max_event)
        head_new = int(math.floor(new_max / cfg.slot_span))
        head_old = (head_new if not math.isfinite(old_max)
                    else int(math.floor(old_max / cfg.slot_span)))
        if head_new > head_old:
            win_w = advance_window(win_w, sb, head_old, head_new,
                                   decay=cfg.decay)
        held = int(sb[slot])
        if late or held > bucket:
            # behind the watermark, or the ring position is already
            # owned by a NEWER bucket (recycled): drop it.  A slot
            # holding an OLDER bucket id is stale — `advance_window`
            # zeroed its mass when it fell out of the W-bucket span —
            # and is simply overwritten.
            return win_c, win_w, sb, False
        scale = float(cfg.decay) ** max(head_new - bucket, 0)
        win_c, win_w, sb = place_summary(
            win_c, win_w, sb, slot, bucket, sc, sw,
            plan=self.cfg.slot_plan(), backend=self.backend, scale=scale)
        return win_c, win_w, sb, True

    # ----------------------------------------------------------- ingest --
    def _place(self, x, w):
        x = jnp.asarray(x, jnp.float32)
        w = (jnp.ones((x.shape[0],), jnp.float32) if w is None
             else jnp.asarray(w, jnp.float32))
        if self.mesh is not None:
            spec = NamedSharding(self.mesh, P(self.data_axes))
            x = jax.device_put(x, spec)
            w = jax.device_put(w, NamedSharding(self.mesh,
                                                P(self.data_axes)))
        return x, w

    def ingest(self, x, w=None, *, ts=None) -> IngestReport:
        """Fold one mini-batch into the windowed model.

        ``ts`` ((n,) per-record event times) is consulted only under
        ``cfg.event_time``; without it each batch is stamped with its
        arrival step (event order == arrival order).  Each call is a
        ``stream.ingest`` span, and the returned report feeds the
        ``stream.*`` counters (records, late drops, births/deaths,
        reseeds) — held to <5% overhead by `tests/test_obs.py`."""
        n_rows = int(np.shape(x)[0])
        with obs.span("stream.ingest", rows=n_rows):
            rep = self._ingest(x, w, ts=ts)
        obs.counter("stream.records").add(n_rows)
        if rep.late_dropped:
            obs.counter("stream.late_dropped").add(rep.late_dropped)
        if rep.born:
            obs.counter("stream.births").add(rep.born)
        if rep.died:
            obs.counter("stream.deaths").add(rep.died)
        if rep.reseeded:
            obs.counter("stream.reseeds").add(1)
        obs.gauge("stream.n_centers").set(rep.n_centers)
        if self._snapshot_listeners:
            self._publish_snapshot()
        return rep

    # ---------------------------------------------------- serve snapshots --
    def add_snapshot_listener(self, fn) -> None:
        """Register ``fn(version, centers, weights)`` to run after every
        ingest with a host copy of the freshest windowed model — the
        serving plane's snapshot publication hook (pass
        ``serve.SnapshotPublisher.publish`` to fan snapshots out to
        hot-swapping scorer replicas).  ``version`` is the stream step,
        monotone across re-seeds; ``centers`` may grow/shrink between
        calls (birth/death)."""
        self._snapshot_listeners.append(fn)

    def _publish_snapshot(self) -> None:
        st = self.state
        version = int(st.step)
        centers = np.asarray(st.centers)
        weights = np.asarray(st.weights)
        for fn in self._snapshot_listeners:
            fn(version, centers, weights)

    def _ingest(self, x, w=None, *, ts=None) -> IngestReport:
        x, w = self._place(x, w)
        if self.state is None:
            self.state = self._fresh_state(
                x, w, jax.random.PRNGKey(self.cfg.seed), reseeds=0, step=0)
        st = self.state
        cfg = self.cfg

        # ---- event-time gate: watermark + late-record drops ----
        wm, wm_gate, n_late, t_batch = float("-inf"), float("-inf"), 0, None
        max_event = st.max_event
        if cfg.event_time:
            ts_np = (np.full((x.shape[0],), float(st.step), np.float64)
                     if ts is None
                     else np.asarray(ts, np.float64).reshape(-1))
            if ts_np.shape[0] != x.shape[0]:
                raise ValueError(f"ts length {ts_np.shape[0]} != batch "
                                 f"rows {x.shape[0]}")
            w_np = np.asarray(w)
            real = w_np > 0
            # gate against the watermark as of BEFORE this batch — a
            # record is late only if the clock had already passed it
            # when it arrived, never relative to its own batch-mates
            old_max = float(st.max_event)
            wm_gate = (float("-inf") if not math.isfinite(old_max)
                       else old_max - cfg.allowed_lateness)
            new_max = old_max
            if real.any():
                new_max = max(new_max, float(ts_np[real].max()))
            wm = new_max - cfg.allowed_lateness   # post-batch watermark
            late = (ts_np < wm_gate) & real
            n_late = int(late.sum())
            if n_late:
                w = jnp.where(jnp.asarray(late), jnp.float32(0), w)
                real = real & ~late
            max_event = jnp.float32(new_max)
            if not real.any():
                # the whole batch is behind the watermark: count + skip
                self.state = st._replace(
                    step=st.step + 1, since_reseed=st.since_reseed + 1,
                    ages=st.ages + 1, max_event=max_event,
                    late_dropped=st.late_dropped + n_late)
                return IngestReport(
                    step=int(self.state.step), drifted=False,
                    reseeded=False, reason="",
                    objective_pre=float("nan"),
                    objective_post=float("nan"), shift=0.0,
                    combiner_iters=np.zeros((1,), np.int32),
                    mass=float(window_mass(st.win_weights)),
                    watermark=wm, late_dropped=n_late,
                    n_centers=int(st.centers.shape[0]))
            t_batch = float(np.median(ts_np[real]))

        # ---- drift probe: objective + residual profile ----
        q_pre = float(self._jq(x, w, st.centers))
        resid = np.asarray(self._jresid(x, st.centers))
        w_np = np.asarray(w)
        real = w_np > 0
        resid_med = float(np.median(resid[real]))
        thr = self.detector.outlier_threshold()
        out_frac = 0.0
        if thr is not None:
            w_tot = float(w_np[real].sum())
            out_frac = float(w_np[(resid > thr) & real].sum()
                             / max(w_tot, 1e-12))

        dcfg = self.detector.cfg
        can_event = int(st.since_reseed) >= cfg.reseed_cooldown
        drifted, reason, born, died = False, "", 0, 0
        if (can_event and self.detector.objective_drifted(q_pre)
                and (thr is None or out_frac > dcfg.reseed_frac)):
            # global regime change: the paper's driver re-seed
            drifted, reason = True, "objective"
            st = self._fresh_state(x, w, st.key, int(st.reseeds) + 1,
                                   int(st.step), carry=st)
            self.detector.reset()
        elif (can_event and thr is not None
                and out_frac >= dcfg.birth_min_frac
                and st.centers.shape[0] < cfg.center_cap()):
            # partial regime change: spawn a center, forget nothing
            born = 1
            st = self._spawn_center(st, x, w, resid)

        def fold(st_in):
            sc, sw, iters = self._jcomb(x, w, st_in.centers)
            if cfg.event_time:
                wc, ww, sb, placed = self._event_place(
                    st_in, sc, sw, t_batch, wm_gate, float(max_event))
                cur = st_in.cursor
            else:
                wc, ww, cur = push_summary(st_in.win_centers,
                                           st_in.win_weights, st_in.cursor,
                                           sc, sw, decay=cfg.decay)
                sb, placed = st_in.slot_buckets, True
            with obs.span("stream.window_merge"):
                mc, mw = self._jmerge(wc, ww)
            sh = float(jnp.max(jnp.linalg.norm(mc - st_in.centers,
                                               axis=-1)))
            return wc, ww, cur, sb, mc, mw, sh, iters, placed

        (win_c, win_w, cursor, slot_b,
         merged_c, merged_w, shift, iters, placed) = fold(st)
        if (not drifted and not born and can_event
                and self.detector.shift_drifted(shift)):
            drifted, reason = True, "shift"
            st = self._fresh_state(x, w, st.key, int(st.reseeds) + 1,
                                   int(st.step), carry=st)
            self.detector.reset()
            (win_c, win_w, cursor, slot_b,
             merged_c, merged_w, shift, iters, placed) = fold(st)
        if not placed:
            # the summary's slot was recycled before it could land (a
            # batch straddling more than the ring span): its records
            # were discarded — count them with the late drops
            n_late += int(np.count_nonzero(np.asarray(w) > 0))

        # ---- cluster death: retire one starved center per batch ----
        ages = st.ages + 1
        if (cfg.death_mass_floor > 0 and not drifted and not born
                and merged_c.shape[0] > 2):
            mw_np = np.asarray(merged_w)
            ages_np = np.asarray(ages)
            floor = cfg.death_mass_floor * mw_np.sum() / mw_np.shape[0]
            starving = (mw_np < floor) & (ages_np >= cfg.window)
            if starving.any():
                idx = int(np.argmin(np.where(starving, mw_np, np.inf)))
                died = 1
                keep = jnp.asarray(np.delete(np.arange(mw_np.shape[0]),
                                             idx))
                merged_c = jnp.take(merged_c, keep, axis=0)
                merged_w = jnp.take(merged_w, keep)
                win_c = jnp.take(win_c, keep, axis=1)
                win_w = jnp.take(win_w, keep, axis=1)
                ages = jnp.take(ages, keep)

        q_post = float(self._jq(x, w, merged_c))
        self.detector.observe(q_pre, shift, drifted or bool(born),
                              resid_med)
        self.state = StreamState(
            centers=merged_c, weights=merged_w,
            win_centers=win_c, win_weights=win_w, cursor=cursor,
            step=st.step + 1,
            since_reseed=(jnp.int32(1) if (drifted or born or died)
                          else st.since_reseed + 1),
            reseeds=st.reseeds, key=st.key,
            slot_buckets=slot_b, ages=ages, max_event=max_event,
            late_dropped=st.late_dropped + n_late,
            births=st.births, deaths=st.deaths + died)
        return IngestReport(
            step=int(self.state.step), drifted=drifted, reseeded=drifted,
            reason=reason, objective_pre=q_pre, objective_post=q_post,
            shift=shift, combiner_iters=np.atleast_1d(np.asarray(iters)),
            mass=float(window_mass(win_w)), watermark=wm,
            late_dropped=n_late, born=born, died=died,
            n_centers=int(merged_c.shape[0]))

    def run(self, batches: Iterable, *, on_report=None):
        """Drive ingest over a loader/source.  Items are ``x`` arrays or
        tuples — ``(x, ts)`` under ``cfg.event_time`` (timestamped
        sources), ``(x, w)`` otherwise (weighted loaders)."""
        reports = []
        for item in batches:
            ts = None
            if isinstance(item, tuple):
                x, second = item
                arr = None if second is None else np.asarray(second)
                if self.cfg.event_time:
                    if arr is not None and np.issubdtype(arr.dtype,
                                                         np.integer):
                        raise ValueError(
                            "run() got an (x, integer-array) tuple under "
                            "event_time — that looks like (records, "
                            "labels) from a synth generator, not "
                            "(records, event times); stamp the stream "
                            "(e.g. data.stamp_source) instead")
                    ts, w = second, None
                else:
                    if arr is not None and arr.dtype == np.float64:
                        raise ValueError(
                            "run() got an (x, float64-array) tuple — "
                            "that is the timestamped-source shape "
                            "(records, event times), but this model has "
                            "event_time=False; enable "
                            "StreamConfig.event_time or pass float32 "
                            "point weights")
                    w = second
                    if arr is not None and np.issubdtype(arr.dtype,
                                                         np.integer):
                        raise ValueError(
                            "run() got an (x, integer-array) tuple — that "
                            "looks like (records, labels) from a synth "
                            "generator, not (records, point weights); pass "
                            "x alone or float weights")
            else:
                x, w = item, None
            rep = self.ingest(x, w, ts=ts)
            reports.append(rep)
            if on_report is not None:
                on_report(rep)
        return reports

    # ------------------------------------------------------------ serve --
    def assign(self, x, *, soft: bool = False):
        """Assignments of ``x`` against the live windowed centers."""
        if self.state is None:
            raise RuntimeError("StreamingBigFCM has ingested no data yet")
        x = jnp.asarray(x, jnp.float32)
        if soft:
            return self.backend.soft_assign(x, self.state.centers,
                                            self.cfg.m)
        return self.backend.hard_assign(x, self.state.centers)

    # ------------------------------------------------------- checkpoint --
    def state_dict(self) -> dict:
        if self.state is None:
            raise RuntimeError("no state to checkpoint yet")
        tree = dict(self.state._asdict())
        for k, v in self.detector.state_arrays().items():
            tree[f"drift_{k}"] = v
        return tree

    def _template(self, d: int) -> dict:
        c, wnd = self.cfg.n_clusters, self.cfg.window
        win_c, win_w = init_window(wnd, c, d)
        z32 = jnp.int32(0)
        tree = dict(StreamState(
            centers=jnp.zeros((c, d), jnp.float32),
            weights=jnp.zeros((c,), jnp.float32),
            win_centers=win_c, win_weights=win_w, cursor=z32, step=z32,
            since_reseed=z32, reseeds=z32,
            key=jax.random.PRNGKey(0),
            slot_buckets=init_slot_buckets(wnd),
            ages=jnp.zeros((c,), jnp.int32),
            max_event=jnp.float32(-jnp.inf), late_dropped=z32,
            births=z32, deaths=z32)._asdict())
        det = DriftDetector(self.cfg.drift)
        for k, v in det.state_arrays().items():
            tree[f"drift_{k}"] = v
        return tree

    def save(self, ckpt) -> None:
        """Persist into an `ft.checkpoint.CheckpointManager`."""
        if self.state is None:
            raise RuntimeError("no state to checkpoint yet")
        ckpt.save(int(self.state.step), self.state_dict())

    @classmethod
    def restore(cls, ckpt, cfg: StreamConfig, d: int, *, mesh=None,
                data_axes: Sequence[str] = ("data",),
                step: Optional[int] = None) -> "StreamingBigFCM":
        """Rebuild a live stream from a checkpoint (d = feature count)."""
        model = cls(cfg, mesh=mesh, data_axes=data_axes)
        tree = ckpt.restore(model._template(d), step)
        det = {k[len("drift_"):]: v for k, v in tree.items()
               if k.startswith("drift_")}
        model.detector.load_state_arrays(det)
        model.state = StreamState(**{k: v for k, v in tree.items()
                                     if not k.startswith("drift_")})
        return model
