"""Traffic of kind ``fit``: whole `bigfcm_fit` calls back to back.

Set-up makes the configuration's data from the seed on the device and
runs one whole fit as warm-up.  The window then runs whole fits back to
back, closed loop, and ends at the end of the first fit to finish after
``seconds``.  Each fit gets a key of its own from a fixed pool of
``key_pool`` keys, in an order drawn from the seed and cycled, so that
every seed fits the same driver samples and seeds, in its own order,
and a seed changes which work comes when, not how much of it there is.
No result is reused.  The harness calls the entry point as a user does
and wraps nothing in a ``jit`` of its own: what the program compiles on
each call is compiled inside the window, and `CompileClock` counts it.

After the window, a sample of the fits drawn from the seed is checked
against the plain reference over all of the data
(`reference.fit_readings`).
"""
from __future__ import annotations

import time

import numpy as np

from bench.harness import reference
from bench.harness.data import make_data

POOL_SEED = 0xF17       # the pool of fit keys, the same for every seed
WARMUP_KEY = 0xFFFFFFFF


class Traffic:
    def __init__(self, cfg: dict, traffic: dict, seed: int, clock,
                 rows: int | None = None):
        import jax

        from repro.core import BigFCMConfig

        self.cfg, self.traffic, self.clock = cfg, traffic, clock
        self.rng = np.random.default_rng(int(seed) % (1 << 64))
        pool = jax.random.PRNGKey(POOL_SEED)
        self.keys = [jax.random.fold_in(pool, int(k)) for k in
                     self.rng.permutation(int(traffic["key_pool"]))]
        self.x = make_data(cfg, seed, rows)
        self.fit_cfg = BigFCMConfig(
            n_clusters=int(cfg["clusters"]), m=float(cfg["m"]),
            combiner_eps=float(cfg["combiner_eps"]),
            backend=cfg["backend"], use_driver=bool(cfg["use_driver"]))
        self._fit(jax.random.fold_in(pool, WARMUP_KEY))

    def _fit(self, key) -> dict:
        from repro.core import bigfcm_fit

        c0, h0 = self.clock.read()
        t0 = time.perf_counter()
        res = bigfcm_fit(self.x, self.fit_cfg, key=key)
        centers = np.asarray(res.centers)          # waits for the fit
        wall = time.perf_counter() - t0
        c1, h1 = self.clock.read()
        d = res.diagnostics
        return {"wall_s": wall, "compile_s": c1 - c0,
                "cache_hits": h1 - h0, "flag": bool(d.flag),
                "driver_s": float(d.t_fcm_driver) + float(d.t_wfcmpb_driver),
                "combiner_iters": [int(v) for v in
                                   np.asarray(d.combiner_iters).ravel()],
                "reducer_iters": int(d.reducer_iters),
                "centers": centers, "masses": np.asarray(res.center_weights)}

    def window(self, seconds: float, annotate=None) -> dict:
        fits = []
        t0 = time.perf_counter()
        while True:
            key = self.keys[len(fits) % len(self.keys)]
            if annotate is not None:
                with annotate(f"bench.fit.{len(fits)}"):
                    fits.append(self._fit(key))
            else:
                fits.append(self._fit(key))
            if time.perf_counter() - t0 >= seconds:
                break
        return {"fits": fits, "window_s": time.perf_counter() - t0}

    def release(self) -> None:
        """Nothing to drop: the reference reads the same rows."""

    def end_to_end(self, rec: dict) -> dict:
        return {"fit_s": rec["window_s"] / len(rec["fits"])}

    def counts(self, rec: dict) -> tuple:
        return len(rec["fits"]), 0

    def readings(self, rec: dict) -> dict:
        """The numbers compared, each the largest over a sample of the
        window's fits drawn from the seed (``check_fits`` of them):
        ``objective_gap``, ``center_shift`` and ``mass_rel``, as
        `reference.fit_readings` defines them."""
        fits = rec["fits"]
        k = min(int(self.traffic["check_fits"]), len(fits))
        picked = sorted(self.rng.choice(len(fits), k, replace=False))
        got = reference.fit_readings(
            self.x, [(fits[i]["centers"], fits[i]["masses"])
                     for i in picked], float(self.cfg["m"]))
        return {"objective_gap": max(g["gap"] for g in got),
                "center_shift": max(g["shift"] for g in got),
                "mass_rel": max(g["mass"] for g in got)}
