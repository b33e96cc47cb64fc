"""Time hard assignment on the chip: the jnp path against the C-tiled
nearest-center kernel, over batch sizes, center counts and kernel tiles;
then score through `ScoringService` at a large C on both paths.

    python scripts/nearest_sweep.py --rows 4096 \\
        --centers 256,1024,4096,16384,65536 [--dim 128] \\
        [--tiles 2048x512,1024x512] [--reps 20] [--seed 0] \\
        [--serve-centers 65536] [--serve-requests 400]

Sweep: for each (rows, centers) it times ``engine.hard_assign`` (the
jnp path: the whole (rows, C) distance matrix, then argmin) and the
kernel at each ``--tiles`` (row tile x C tile), and prints one JSON line
each.  ``device_ms`` is the device's busy time a call: ``--reps`` calls
run back to back under the profiler after a warm-up call, and the union
of the device operations' intervals in the trace, over ``--reps``.
``wall_ms`` is the host's time a call over the same calls, for
comparison.  Each kernel line also gives the share of rows on which it
agrees with the jnp path, and of 32 rows checked against the float64
reference (a center within ``SAME_CENTER`` of the nearest is right).
The rows are centers plus noise, so each has a clear nearest center.
`repro.kernels.ops.NEAREST_MIN_CENTERS` is set from the device column
at the service's largest batch.

Serve (``--serve-centers``, 0 skips it): one `ScoringService` with the
default `ServiceConfig` per backend, ``jnp`` then ``pallas``, against
the same centers; the shape rule is lowered to that C if it lies above,
so that the ``pallas`` backend runs the kernel.  Every bucket of the ladder is warmed first, then
``--serve-requests`` requests of log-uniform 1-4,096 rows are submitted
at once and awaited; the line gives rows/s over that burst,
``serve.tiled`` against ``serve.batches``, and the share of 512 sampled
rows whose label is right by the float64 reference.  Exits non-zero
off a TPU or where a check fails.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))

CHECK_ROWS = 32
SERVE_CHECK_ROWS = 512
TRACE_DIR = os.path.join(ROOT, ".scratch", "nearest_sweep")


def _ints(text: str) -> list:
    return [int(v) for v in text.split(",") if v]


def _busy_ns(path: str) -> float:
    """Total length of the merged intervals in which any operation ran
    on a device plane of the ``.xplane.pb`` trace at ``path``."""
    from jax.profiler import ProfileData

    spans = sorted(
        (float(e.start_ns), float(e.start_ns + e.duration_ns))
        for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/device:") and "CUSTOM" not in plane.name
        for line in plane.lines if line.name == "XLA Ops"
        for e in line.events)
    busy, end = 0.0, float("-inf")
    for s, e in spans:
        if e > end:
            busy += e - max(s, end)
            end = e
    return busy


def _time_ms(fn, args, reps: int) -> dict:
    """``wall_ms`` and ``device_ms`` a call of ``fn(*args)``, warmed."""
    import jax

    jax.block_until_ready(fn(*args))                # compile, warm up
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    with jax.profiler.trace(TRACE_DIR):
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn(*args)
        jax.block_until_ready(out)
        wall = time.perf_counter() - t0
    path, = glob.glob(os.path.join(TRACE_DIR, "**", "*.xplane.pb"),
                      recursive=True)
    busy = _busy_ns(path)
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    return {"wall_ms": 1e3 * wall / reps, "device_ms": 1e-6 * busy / reps}


def sweep(args, dev) -> None:
    import jax
    import numpy as np
    from functools import partial

    from repro.engine import hard_assign
    from repro.kernels.nearest import nearest_center_pallas
    from repro.kernels.ref import nearest_center_right

    tiles = [tuple(int(v) for v in t.split("x"))
             for t in args.tiles.split(",") if t]
    jnp_path = jax.jit(hard_assign)
    key = jax.random.PRNGKey(args.seed)
    for c in _ints(args.centers):
        kv, kx, kn, key = jax.random.split(key, 4)
        v = 4.0 * jax.random.normal(kv, (c, args.dim), "float32")
        for n in _ints(args.rows):
            pick = jax.random.randint(kx, (n,), 0, c)
            x = v[pick] + jax.random.normal(kn, (n, args.dim), "float32")
            want = np.asarray(jnp_path(x, v))
            line = {"device": dev.device_kind, "rows": n, "centers": c,
                    "dim": args.dim}
            print(json.dumps(dict(line, path="jnp", **_time_ms(
                jnp_path, (x, v), args.reps))), flush=True)
            for tn, tc in tiles:
                fn = jax.jit(partial(nearest_center_pallas, tile_n=tn,
                                     tile_c=tc))
                times = _time_ms(fn, (x, v), args.reps)
                got = np.asarray(fn(x, v))
                k = min(CHECK_ROWS, n)
                right = nearest_center_right(np.asarray(x[:k]),
                                             np.asarray(v), got[:k])
                print(json.dumps(dict(
                    line, path="kernel", tile_n=tn, tile_c=tc, **times,
                    agree_jnp=float(np.mean(got == want)),
                    right_f64=float(np.mean(right)))), flush=True)


def serve(args, dev) -> bool:
    """Score one burst through `ScoringService` on each backend; whether
    every check held."""
    import numpy as np

    from repro import obs
    from repro.kernels import ops
    from repro.kernels.ref import SAME_CENTER, nearest_center_ref
    from repro.serve import (CenterSnapshot, Scorer, ScoringService,
                             ServiceConfig)

    c, d = args.serve_centers, args.dim
    # the pallas backend takes the kernel at this C, whatever the
    # constant holds: this phase checks the kernel's path
    ops.NEAREST_MIN_CENTERS = min(ops.NEAREST_MIN_CENTERS, c)
    rng = np.random.default_rng(args.seed)
    centers = (4.0 * rng.standard_normal((c, d))).astype(np.float32)
    sizes = np.exp(rng.uniform(0.0, np.log(4096.0),
                               args.serve_requests)).astype(int)
    reqs = [(centers[rng.integers(0, c, n)]
             + rng.standard_normal((n, d))).astype(np.float32)
            for n in np.maximum(sizes, 1)]
    rows = int(sum(len(r) for r in reqs))
    pick = rng.choice(rows, min(SERVE_CHECK_ROWS, rows), replace=False)
    best, _ = nearest_center_ref(np.concatenate(reqs)[pick], centers)
    cfg = ServiceConfig()
    ok = True
    for backend in ("jnp", "pallas"):
        obs.reset_metrics()
        scorer = Scorer(CenterSnapshot(0, centers), backend=backend)
        with ScoringService(scorer, cfg) as svc:
            t0 = time.perf_counter()
            for b in svc.buckets:                    # warm every bucket
                svc.score(np.zeros((b, d), np.float32), 600)
            warm = time.perf_counter() - t0
            obs.reset_metrics()
            t0 = time.perf_counter()
            futs = [svc.submit(r) for r in reqs]
            results = [f.result(600) for f in futs]
            secs = time.perf_counter() - t0
        batches = int(obs.counter("serve.batches", replica="r0").value)
        tiled = int(obs.counter("serve.tiled", replica="r0").value)
        got = np.concatenate([r.assignments for r in results])[pick]
        # the reference's rule: a center within SAME_CENTER of the
        # float64 nearest one is right
        gap = np.linalg.norm(centers[got].astype(np.float64)
                             - centers[best], axis=1)
        right = float(np.mean(gap <= SAME_CENTER))
        ok &= right == 1.0 and (tiled == batches) == (backend == "pallas")
        print(json.dumps({
            "device": dev.device_kind, "path": "serve", "backend": backend,
            "centers": c, "dim": d, "requests": len(reqs), "rows": rows,
            "warm_s": warm, "seconds": secs, "rows_per_s": rows / secs,
            "batches": batches, "tiled": tiled, "right_f64": right,
            "traces": scorer.traces}), flush=True)
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", default="4096")
    ap.add_argument("--centers", default="256,1024,4096,16384,65536")
    ap.add_argument("--dim", type=int, default=128)
    ap.add_argument("--tiles", default="2048x512")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--serve-centers", type=int, default=65536)
    ap.add_argument("--serve-requests", type=int, default=400)
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"nearest_sweep: no TPU (JAX found {dev.platform})")
    sweep(args, dev)
    if args.serve_centers and not serve(args, dev):
        print("nearest_sweep: a serve check failed", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
