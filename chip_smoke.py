"""Bring-up check: BigFCM's main path on a TPU, at the paper's KDD Cup 99
size, through the entry points a user calls.

    python chip_smoke.py [--seed N]             # one chip
    python chip_smoke.py --chips 4 [--seed N]   # the four-chip mesh fit

One chip: generate the KDD Cup 99 shape (4,898,431 records x 41
features, 23 skewed classes), fit it with `bigfcm_fit` on the ``pallas``
backend (driver race, combiner, reducer), check the fit against a plain
float32 reference, then serve the fitted centers through a
`ScoringService` to concurrent clients and check their answers.

Four chips: the `shard_map` combiner/reducer fit on a mesh of all four
chips, and the one-chip fit of the same data and seed on device 0;
their global objectives, and their centers at the classes that hold
most of the rows, must agree.

The script needs a TPU: anywhere else it exits non-zero before any
work.  Every tolerance is fixed below, before any run.  Every check
prints its measured value beside its tolerance; any failed check exits
non-zero.  When all pass, the last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
The times printed are one cold run each (compilation included where it
says so), not benchmark results.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

ROOT = os.path.dirname(os.path.abspath(__file__))

KDD_ROWS = 4_898_431          # KDD Cup 99, paper Table 3
N_CLUSTERS = 23
M = 2.0
REF_BLOCK = 65_536            # reference pass: rows per (block, C, d) step
FIXED_POINT_SWEEPS = 20

# Tolerances, fixed before the first chip run from a CPU rehearsal at
# reduced rows (PERF.md, Findings).
TOL_KERNEL_REL = 1e-3     # one kernel sweep vs reference: v_num, w_i, q
TOL_FIXED_POINT_REL = 1e-4  # reference FCM from the fit: |ΔJ| / J
TOL_RECOVERY = 1.5        # mean matched distance to heavy class means
TOL_TIE_REL = 1e-4        # near-tie: d²-gap ≤ this · (|x|² + |v|²)
TOL_SAME_CENTER = 1e-3    # centers closer than this are one point
TOL_MESH_CENTER = 0.5     # mesh vs one-chip: centers at the heavy classes
TOL_MESH_OBJ_REL = 5e-2   # mesh vs one-chip fit: global objective


def log(msg: str) -> None:
    print(msg, flush=True)


class Checks:
    """Measured value against a fixed tolerance, printed side by side."""

    def __init__(self):
        self.failed = []

    def le(self, name: str, value: float, tol: float) -> None:
        value = float(value)
        ok = value <= tol                 # NaN fails
        log(f"check {name}: {value!r} <= {tol!r} {'ok' if ok else 'FAIL'}")
        if not ok:
            self.failed.append(name)

    def true(self, name: str, ok: bool, detail: str = "") -> None:
        log(f"check {name}: {detail} {'ok' if ok else 'FAIL'}")
        if not ok:
            self.failed.append(name)


def require_tpu(n_chips: int):
    """The device JAX found, or exit non-zero naming what it found."""
    import jax

    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU: JAX found {len(devs)} "
                 f"{d0.platform} device(s) ({d0.device_kind}); nothing run")
    if len(devs) < n_chips:
        sys.exit(f"chip_smoke: --chips {n_chips} needs {n_chips} TPU "
                 f"chips, JAX found {len(devs)}")
    log(f"device: platform={d0.platform} kind={d0.device_kind} "
        f"count={len(devs)} jax={jax.__version__}")
    return devs


class CompileClock:
    """Seconds spent lowering and compiling XLA programs, and persistent
    cache hits, from JAX's monitoring events."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def timed_fit(clock: CompileClock, label: str, fit):
    """Run ``fit()`` once, cold; print compile and run seconds."""
    import jax

    c0, h0, t0 = clock.seconds, clock.cache_hits, time.perf_counter()
    res = fit()
    jax.block_until_ready(res.centers)
    wall = time.perf_counter() - t0
    comp = clock.seconds - c0
    log(f"{label}: one cold run: wall_s={wall!r} compile_s={comp!r} "
        f"run_s={wall - comp!r} persistent_cache_hits="
        f"{clock.cache_hits - h0}")
    d = res.diagnostics
    log(f"{label}: driver flag={bool(d.flag)} sample={d.sample_size} "
        f"combiner_iters={[int(i) for i in d.combiner_iters]} "
        f"reducer_iters={int(d.reducer_iters)}")
    return res


# ------------------------------------------------------------ reference --

def reference_sweep(x, w, centers, m: float = M):
    """Plain float32 FCM sweep, independent of the code under test:
    direct differences ‖x−v‖² (no expansion), Eq. 5 memberships, and
    row blocks summed in order.  Returns raw (v_num, w_i, q)."""
    import jax
    import jax.numpy as jnp

    n, d = x.shape
    pad = -n % REF_BLOCK
    xb = jnp.pad(x, ((0, pad), (0, 0))).reshape(-1, REF_BLOCK, d)
    wb = jnp.pad(w, (0, pad)).reshape(-1, REF_BLOCK)

    def block(carry, xw):
        xi, wi = xw
        d2 = jnp.sum((xi[:, None, :] - centers[None]) ** 2, axis=-1)
        d2 = jnp.maximum(d2, 1e-12)
        r = d2 ** (-1.0 / (m - 1.0))
        um = (r / jnp.sum(r, axis=1, keepdims=True)) ** m * wi[:, None]
        v_num, w_i, q = carry
        return (v_num + um.T @ xi, w_i + um.sum(0),
                q + jnp.sum(um * d2)), None

    c = centers.shape[0]
    init = (jnp.zeros((c, d), jnp.float32), jnp.zeros((c,), jnp.float32),
            jnp.float32(0.0))
    with jax.default_matmul_precision("highest"):
        out, _ = jax.lax.scan(block, init, (xb, wb))
    return out


def reference_fcm(x, w, centers, sweeps: int):
    """``sweeps`` plain reference FCM iterations from ``centers``:
    returns (objective at the start, objective at the end, max center
    shift)."""
    import jax
    import jax.numpy as jnp

    def step(v, _):
        v_num, w_i, q = reference_sweep(x, w, v)
        return v_num / w_i[:, None], q

    v_end, qs = jax.lax.scan(step, centers, None, length=sweeps)
    q_end = reference_sweep(x, w, v_end)[2]
    shift = jnp.max(jnp.sqrt(jnp.sum((v_end - centers) ** 2, -1)))
    return qs[0], q_end, shift


def rel(a, b) -> float:
    import numpy as np

    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


# ----------------------------------------------------------------- data --

def make_data(seed: int, n: int):
    import jax
    import numpy as np

    from repro.data.synth import make_kdd_like

    t0 = time.perf_counter()
    x, labels = make_kdd_like(n, seed=seed)
    x_dev = jax.block_until_ready(jax.device_put(x))
    log(f"setup: generated and uploaded {n} x {x.shape[1]} f32 "
        f"({x.nbytes / 1e9:.3f} GB, {len(np.unique(labels))} classes) "
        f"in {time.perf_counter() - t0:.1f}s")
    return x, labels, x_dev


def heavy_class_means(x_dev, labels):
    """Means of the mixture classes that hold at least 1/C of the rows.

    At m = 2 FCM spends several centers on each dominant class and none
    on the classes that hold a few per cent of the rows: that placement
    has the lower objective.  So recovery is judged on these classes."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    n = x_dev.shape[0]
    lab = jnp.asarray(labels)
    sums = jax.ops.segment_sum(x_dev, lab, N_CLUSTERS)
    counts = np.asarray(jax.ops.segment_sum(
        jnp.ones((n,), jnp.float32), lab, N_CLUSTERS))
    heavy = counts >= n / N_CLUSTERS
    log(f"recovery: {int(heavy.sum())} classes hold >= 1/{N_CLUSTERS} of "
        f"the rows ({float(counts[heavy].sum()) / n!r} of them)")
    return (np.asarray(sums) / np.maximum(counts, 1.0)[:, None])[heavy]


def nearest_to(means, v):
    """The center of ``v`` nearest each of ``means``."""
    import numpy as np

    v = np.asarray(v, np.float64)
    d = np.linalg.norm(means[:, None] - v[None], axis=-1)
    return v[np.argmin(d, axis=1)]


# ------------------------------------------------------------- one chip --

def one_chip(seed: int, n: int = KDD_ROWS) -> Checks:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import BigFCMConfig, bigfcm_fit
    from repro.core.metrics import match_centers
    from repro.engine import get_backend
    from repro.kernels.ops import interpret_mode

    checks = Checks()
    clock = CompileClock()
    x, labels, x_dev = make_data(seed, n)
    cfg = BigFCMConfig(n_clusters=N_CLUSTERS, m=M, backend="pallas",
                       seed=seed)
    res = timed_fit(clock, "fit", lambda: bigfcm_fit(x_dev, cfg))
    centers = res.centers

    # -- the sweep ran as a compiled Mosaic kernel, not interpreted --
    be = get_backend("pallas")
    w = jnp.ones((n,), jnp.float32)
    sweep = jax.jit(lambda a, b, v: be.accumulate(a, b, v, M))
    hlo = sweep.lower(x_dev, w, centers).as_text()
    checks.true("kernel compiled", "tpu_custom_call" in hlo
                and not interpret_mode(),
                f"backend={be.name} tpu_custom_call="
                f"{'tpu_custom_call' in hlo} interpret={interpret_mode()}")

    # -- one kernel sweep at the fitted centers against the reference --
    got = jax.block_until_ready(sweep(x_dev, w, centers))
    want = jax.block_until_ready(jax.jit(reference_sweep)(x_dev, w,
                                                          centers))
    for name, g, e in zip(("v_num", "w_i", "q"), got, want):
        checks.le(f"kernel sweep {name} rel err", rel(g, e), TOL_KERNEL_REL)

    # -- the fit is a fixed point of reference FCM --
    q0, q1, shift = jax.jit(reference_fcm, static_argnums=3)(
        x_dev, w, centers, FIXED_POINT_SWEEPS)
    log(f"fixed point: J(fit)={float(q0)!r} J(+{FIXED_POINT_SWEEPS} "
        f"reference sweeps)={float(q1)!r} max center shift="
        f"{float(shift)!r} (fit's own objective field: "
        f"{float(res.objective)!r}, reducer-level, not compared)")
    checks.le("fixed point |dJ|/J", abs(float(q0) - float(q1))
              / float(q0), TOL_FIXED_POINT_REL)

    # -- the centers recover the mixture's heavy class means --
    checks.le("recovery mean matched distance to heavy class means",
              match_centers(np.asarray(centers),
                            heavy_class_means(x_dev, labels)), TOL_RECOVERY)

    serve(checks, x, np.asarray(centers), seed)
    return checks


def serve(checks: Checks, x, centers, seed: int) -> None:
    """Publish the centers and answer concurrent clients; compare every
    hard assignment with a float64 host argmin."""
    import numpy as np

    from repro.serve import CenterSnapshot, Scorer, ScoringService, \
        ServiceConfig

    version = 1
    sizes = [1, 3, 64, 500, 4096, 10_000, 65_536, 33_333, 1, 2_048,
             12_345, 65_536]
    rng = np.random.default_rng(seed + 1)
    reqs = []
    for s in sizes:
        lo = int(rng.integers(0, x.shape[0] - s + 1))
        reqs.append(x[lo:lo + s])
    scorer = Scorer(CenterSnapshot(version, centers), m=M,
                    backend="pallas", replica="chip0")
    cfg = ServiceConfig(queue_rows=1 << 18, deadline_s=600.0)
    t0 = time.perf_counter()
    with ScoringService(scorer, cfg) as svc:
        with ThreadPoolExecutor(len(reqs)) as pool:
            results = list(pool.map(lambda r: svc.score(r, timeout=600),
                                    reqs))
    log(f"serve: {len(reqs)} concurrent requests, {sum(sizes)} rows, "
        f"one cold run {time.perf_counter() - t0:.2f}s (compiles "
        f"included), programs={svc.compile_counts()}")
    checks.true("serve versions", all(r.version == version
                                      for r in results),
                f"versions={sorted({r.version for r in results})} "
                f"published={version}")
    # FCM may return coincident centers (several on one dominant class):
    # an answer counts as right when it names any center of the right
    # point.  Rows whose two nearest distinct points are a near-tie
    # are not compared.
    c64 = centers.astype(np.float64)
    gap_cc = np.linalg.norm(c64[:, None] - c64[None], axis=-1)
    point = np.argmax(gap_cc <= TOL_SAME_CENTER, axis=1)   # first alias
    distinct = np.unique(point)
    ties = mismatched = 0
    for req, r in zip(reqs, results):
        x64 = req.astype(np.float64)
        d2 = ((x64[:, None, :] - c64[None, distinct]) ** 2).sum(-1)
        order = np.argsort(d2, axis=1)
        rows = np.arange(len(x64))
        best = distinct[order[:, 0]]
        gap = d2[rows, order[:, 1]] - d2[rows, order[:, 0]]
        scale = (x64 ** 2).sum(1) + (c64[best] ** 2).sum(1)
        tie = gap <= TOL_TIE_REL * scale
        wrong = point[np.asarray(r.assignments)] != best
        ties += int(tie.sum())
        mismatched += int((wrong & ~tie).sum())
    log(f"serve: {len(distinct)} distinct points among {len(c64)} centers "
        f"(distance <= {TOL_SAME_CENTER}); near-ties (d2 gap <= "
        f"{TOL_TIE_REL} * (|x|^2+|v|^2)): {ties} of {sum(sizes)} rows")
    checks.le("serve mismatches outside near-ties", mismatched, 0)


# ----------------------------------------------------------- four chips --

def four_chips(seed: int, n: int = KDD_ROWS) -> Checks:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import BigFCMConfig, bigfcm_fit
    from repro.core.metrics import match_centers
    from repro.launch.mesh import make_host_mesh

    checks = Checks()
    clock = CompileClock()
    x, labels, x_dev = make_data(seed, n)
    # The driver race is timed on the wall clock, so two fits may pick
    # different combiners (FCM or WFCMPB) and reach different fixed
    # points.  Both fits here start from the same random seeds instead
    # and run FCM combiners: the comparison isolates the mesh
    # combiner/reducer.  The one-chip phase covers the race.
    cfg = BigFCMConfig(n_clusters=N_CLUSTERS, m=M, backend="pallas",
                       use_driver=False, seed=seed)
    mesh = make_host_mesh()
    log(f"mesh: {dict(mesh.shape)}")
    res4 = timed_fit(clock, "mesh fit",
                     lambda: bigfcm_fit(x, cfg, mesh=mesh))
    for d in jax.devices():
        st = d.memory_stats() or {}
        log(f"bytes on device {d.id}: in_use={st.get('bytes_in_use')} "
            f"peak={st.get('peak_bytes_in_use')}")
    res1 = timed_fit(clock, "one-chip fit",
                     lambda: bigfcm_fit(x_dev, cfg))
    # Four combiners and one are different runs of FCM: they reach
    # nearby fixed points, not one and the same, and the reducer keeps
    # the first combiner's coincident centers.  Centers are compared
    # where both fits must put one, at the heavy classes.
    v4, v1 = np.asarray(res4.centers), np.asarray(res1.centers)
    w = jnp.ones((n,), jnp.float32)
    obj = jax.jit(lambda a, b, v: reference_sweep(a, b, v)[2])
    q4, q1 = float(obj(x_dev, w, v4)), float(obj(x_dev, w, v1))
    log(f"global objective (reference pass): mesh={q4!r} one-chip={q1!r}; "
        f"all centers mean matched distance="
        f"{float(match_centers(v4, v1))!r} (not compared)")
    # The mesh fit's own objective is the psum of the per-chip sweeps.
    checks.le("mesh fit's own objective vs reference pass rel err",
              rel(res4.objective, q4), TOL_KERNEL_REL)
    checks.le("mesh vs one-chip objective rel diff", abs(q4 - q1) / q1,
              TOL_MESH_OBJ_REL)
    means = heavy_class_means(x_dev, labels)
    checks.le("mesh vs one-chip centers at the heavy classes: mean distance",
              float(np.mean(np.linalg.norm(nearest_to(means, v4)
                                           - nearest_to(means, v1),
                                           axis=1))), TOL_MESH_CENTER)
    for name, v in (("mesh", v4), ("one-chip", v1)):
        checks.le(f"{name} recovery mean matched distance to heavy class "
                  "means", match_centers(v, means), TOL_RECOVERY)
    return checks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    devs = require_tpu(args.chips)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    t0 = time.perf_counter()
    checks = (four_chips if args.chips == 4 else one_chip)(args.seed)
    log(f"total: {time.perf_counter() - t0:.1f}s")
    if checks.failed:
        log(f"FAILED: {checks.failed}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
