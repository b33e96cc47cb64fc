"""Traffic of kind ``serve_nearest``: the ``serve`` kind's open-loop
scoring of hard labels, at a center count where the ``serve`` kind's
check cannot run.

Everything but the records, the check and one counter is the ``serve``
kind's: the published centers (``clusters`` records of the pool drawn
by the seed), one `Scorer` behind a `ScoringService` with its default
`ServiceConfig`, the warm-up, the schedule and the window.

- The records: the mixture ``uniform_classes`` at the configuration's
  ``classes``, drawn as `bench.harness.data` draws it (the class means
  from ``means_key``, spread ``spread`` around them) except for each
  record's class, drawn uniformly by `jax.random.randint`.  That is the
  same law: `make_data` draws it with `jax.random.categorical`, whose
  Gumbel draw is one sample per (record, class), 6.9e10 of them for a
  pool of 1,048,576 records over 65,536 classes.
- The check: `bench.harness.nearest.nearest_gaps`, the float64 nearest
  center found by blocks of centers, on ``check_requests`` responses
  drawn from the seed.
- The counter: ``serve.tiled`` of the replica (batches whose labels came
  from the program's tiled nearest-center kernel), over the window, as
  ``tiled`` beside the ``serve`` kind's counters; None where the
  program has no such counter.
"""
from __future__ import annotations

from functools import partial

import numpy as np

from bench.harness.data import DATA_KEY, seed_key
from bench.harness.nearest import nearest_gaps
from bench.kinds import serve

TILED = "serve.tiled{replica=" + serve.REPLICA + "}"


def make_pool(cfg: dict, seed: int, rows: int):
    """``rows`` records of the configuration's ``uniform_classes``
    mixture for ``seed``, on the default device."""
    import jax
    import jax.numpy as jnp

    mix = cfg["mixture"]
    if mix["kind"] != "uniform_classes":
        raise ValueError(f"serve_nearest draws uniform_classes, not "
                         f"{mix['kind']!r}")

    @partial(jax.jit, static_argnames=("n", "k", "d"))
    def draw(key, means_key, n: int, k: int, d: int):
        _, k_lab, k_noise = jax.random.split(key, 3)
        means = float(mix["sep"]) * jax.random.normal(means_key, (k, d),
                                                      jnp.float32)
        labels = jax.random.randint(k_lab, (n,), 0, k)
        return means[labels] + float(mix["spread"]) * jax.random.normal(
            k_noise, (n, d), jnp.float32)

    key = jax.random.fold_in(seed_key(seed), DATA_KEY)
    return jax.block_until_ready(draw(
        key, jax.random.PRNGKey(int(mix["means_key"])), int(rows),
        int(mix["classes"]), int(cfg["features"])))


class Traffic(serve.Traffic):
    def __init__(self, cfg: dict, traffic: dict, seed: int, clock,
                 rows: int | None = None):
        """The ``serve`` kind's set-up, with the pool from `make_pool`
        and hard labels always."""
        from repro.serve import (CenterSnapshot, Scorer, ScoringService,
                                 ServiceConfig)

        self.cfg, self.traffic = cfg, traffic
        self.soft = False
        self.rng = np.random.default_rng(int(seed) % (1 << 64))
        pool_rows = int(rows or traffic["pool_rows"])
        self.pool = np.asarray(make_pool(cfg, seed, pool_rows))
        idx = self.rng.choice(pool_rows, int(cfg["clusters"]),
                              replace=False)
        self.centers = self.pool[np.sort(idx)]
        scorer = Scorer(CenterSnapshot(1, self.centers), m=float(cfg["m"]),
                        backend=cfg["backend"], replica=serve.REPLICA)
        self.svc = ScoringService([scorer], ServiceConfig())
        for b in self.svc.buckets:
            self.svc.score(self.pool[:b])
        self._send(self._schedule(serve.WARMUP_REQUESTS
                                  / traffic["rate_per_s"]))

    @staticmethod
    def _tiled():
        from repro import obs

        return obs.metrics_snapshot()["counters"].get(TILED)

    def window(self, seconds: float, annotate=None) -> dict:
        before = self._tiled()
        rec = super().window(seconds, annotate)
        after = self._tiled()
        rec["obs"]["tiled"] = (None if after is None
                               else after - (before or 0.0))
        return rec

    def _gap(self, x: np.ndarray, got: np.ndarray) -> float:
        return float(nearest_gaps(x, got, self.centers).max())
