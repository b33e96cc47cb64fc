"""Run one cell of the chip benchmark and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one cell of `BENCHMARK.json`: set up (data from the seed,
warm-up), measure for ``--seconds``, then check what the window
produced against the plain references in `bench/harness/reference.py`.
With ``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` the window runs under the profiler and the metrics are
the cell's per-layer metrics, each read by ``bench/metrics/<name>.py``.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown``
when traced), and last ``checks``, each number compared with its limit;
the same comparisons are the last lines of standard error.  Without a
TPU, or with fewer chips than the cell asks for, the run exits non-zero
before any work and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def require_chips(n: int):
    """The devices JAX found, or exit non-zero naming what it found."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"bench: no TPU: JAX found {len(devs)} {devs[0].platform} "
                 f"device(s); nothing run")
    if len(devs) < n:
        sys.exit(f"bench: the cell needs {n} TPU chips, JAX found "
                 f"{len(devs)}; nothing run")
    return devs


def traffic_kind(kind: str):
    """The ``Traffic`` class of ``bench/kinds/<kind>.py``: each kind of
    traffic is a module of its own, found by its name."""
    import importlib

    if not kind.isidentifier():
        raise ValueError(f"{kind!r} is not a traffic kind")
    return importlib.import_module(f"bench.kinds.{kind}").Traffic


def compare(readings: dict, limits: dict) -> dict:
    """Each number beside its limit; a number without a limit, or a
    limit without a number, is an error of the benchmark's files."""
    if set(readings) != set(limits):
        raise ValueError(f"readings {sorted(readings)} do not match "
                         f"limits {sorted(limits)}")
    return {k: {"value": float(readings[k]), "limit": float(limits[k])}
            for k in sorted(readings)}


def is_correct(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())  # NaN fails


def run_cell(cell, seed: int, seconds: float, trace: bool, devs,
             rows=None) -> dict:
    """Set up, measure and check one cell; returns the result object.
    ``rows`` overrides the configuration's size (tests only)."""
    import jax

    from bench.harness import trace as tr
    from bench.harness.cell import metric_reader
    from bench.harness.clock import CompileClock

    clock = CompileClock()
    kind = traffic_kind(cell.traffic["kind"])
    traffic = kind(cell.config, cell.traffic, seed, clock, rows=rows)
    setup_s = time.perf_counter() - T_START
    log(f"bench: {cell.name} seed={seed} set-up {setup_s:.3f}s")

    reduced = None
    if trace:
        tdir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=opts)
        try:
            t_epoch = time.time()
            with jax.profiler.TraceAnnotation(tr.WINDOW):
                rec = traffic.window(seconds, jax.profiler.TraceAnnotation)
        finally:
            jax.profiler.stop_trace()
        path = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                         recursive=True)
        t0 = time.perf_counter()
        reduced = tr.with_host_spans(tr.load(path[0]), clock.spans)
        shutil.rmtree(tdir, ignore_errors=True)
        log(f"bench: trace read in {time.perf_counter() - t0:.3f}s; window "
            f"mark {reduced.start_ns / 1e6:.3f} ms from the trace's zero, "
            f"wall clock says {(t_epoch * 1e9 - reduced.epoch_ns) / 1e6:.3f}")
    else:
        rec = traffic.window(seconds)

    used = devs[:cell.chips]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in used)
    traffic.release()
    attempted, failed = traffic.counts(rec)
    t0 = time.perf_counter()
    checks = compare(traffic.readings(rec), cell.limits)
    log(f"bench: checked in {time.perf_counter() - t0:.3f}s")

    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    metrics = {}
    if trace:
        run = types.SimpleNamespace(cell=cell, record=rec, trace=reduced,
                                    device_kind=devs[0].device_kind)
        for m in cell.per_layer:
            v = metric_reader(m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": units[
                    m["name"]]}
    else:
        values = dict(traffic.end_to_end(rec), setup_s=setup_s)
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": float(values[m["name"]]),
                                  "unit": units[m["name"]]}

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}
    out = {"correct": is_correct(checks), "attempted": attempted,
           "failed": failed, "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = tr.mean_busy_s(reduced)
        device["window_s"] = reduced.window_ns / 1e9
        out["breakdown"] = tr.breakdown(reduced)
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench.harness.cell import load_cell

    cell = load_cell(args.workload)
    devs = require_chips(cell.chips)
    from repro.launch.cache import enable_compile_cache

    log(f"bench: compile cache {enable_compile_cache()}")
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), devs)
    for k, c in out["checks"].items():
        ok = c["value"] <= c["limit"]
        log(f"check {k}: {c['value']!r} <= {c['limit']!r} "
            f"{'ok' if ok else 'FAIL'}")
    print(json.dumps(out, allow_nan=True), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
