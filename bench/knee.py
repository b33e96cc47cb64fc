"""Find the scoring knee once, on the chip: the highest offered rate at
which the service keeps its p99 within a limit with no growing backlog.

    python bench/knee.py --workload kdd99.serve-over --seed <n> --seconds 10 \\
        --rates 100,200,400,800 --p99-limit-ms 100

One process sets up the cell's service once, then runs one window per
rate, lowest first, with the cell's request sizes.  For each rate it
prints the offered and completed requests per second, p50 and p99 from
the due time, the p50 of the last tenth of requests against the first
tenth (a backlog that grows shows as a rise), how late the generator
ran, the rows answered inside the window per second, how long answers
came after it, and how often the cgroup's CPU quota throttled the
process.  A cell's own rate is then set by hand in
``bench/traffic/<traffic>.json``: about 4/5 of the knee for a cell
below it, or about 1.3 times the requests per second answered inside
the window at overload for a cell above it.
"""
from __future__ import annotations

import argparse
import gc
import json
import time
import os
import sys

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                os.path.join(os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__))), "src")]

import numpy as np  # noqa: E402


def cpu_throttled() -> list:
    """``[nr_throttled, throttled_usec]`` of this process's cgroup (v2),
    or zeros where the file is not there."""
    try:
        with open("/sys/fs/cgroup/cpu.stat") as f:
            stat = dict(line.split() for line in f)
        return [int(stat["nr_throttled"]), int(stat["throttled_usec"])]
    except (OSError, KeyError, ValueError):
        return [0, 0]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--p99-limit-ms", type=float, default=100.0)
    args = ap.parse_args(argv)

    from bench.harness.cell import load_cell
    from bench.harness.clock import CompileClock
    from bench.kinds.serve import Traffic, nearest_rank
    from bench.run import require_chips
    from repro.launch.cache import enable_compile_cache

    cell = load_cell(args.workload)
    require_chips(cell.chips)
    enable_compile_cache()
    traffic = dict(cell.traffic)
    srv = Traffic(cell.config, traffic, args.seed, CompileClock())
    knee = None
    pauses = []

    def on_gc(phase, info, t={}):
        if phase == "start":
            t["t"] = time.perf_counter()
        elif "t" in t:
            pauses.append((info["generation"], time.perf_counter() - t["t"]))
    gc.callbacks.append(on_gc)
    for rate in (float(r) for r in args.rates.split(",")):
        traffic["rate_per_s"] = rate
        del pauses[:]
        throttled = cpu_throttled()
        rec = srv.window(args.seconds)
        throttled = [b - a for a, b in zip(throttled, cpu_throttled())]
        lat = srv.latencies(rec)
        n = len(lat)
        tenth = max(n // 10, 1)
        row = {
            "rate_per_s": rate, "requests": n,
            "completed_per_s": float(np.isfinite(lat).sum()) / args.seconds,
            "window_records_per_s": srv.end_to_end(rec)[
                "score_records_per_s"],
            "drain_s": float(np.nanmax(rec["done"]) - rec["t0"]
                             - args.seconds),
            "p50_ms": 1e3 * nearest_rank(lat, 0.5),
            "p99_ms": 1e3 * nearest_rank(lat, 0.99),
            "first_tenth_p50_ms": 1e3 * nearest_rank(lat[:tenth], 0.5),
            "last_tenth_p50_ms": 1e3 * nearest_rank(lat[-tenth:], 0.5),
            "gen_lag_p99_ms": 1e3 * nearest_rank(rec["sent"] - rec["due"],
                                                 0.99),
            "batch_rows": rec["obs"]["records"] / max(rec["obs"]["batches"],
                                                      1),
            "gc_gen2": sum(1 for g, _ in pauses if g == 2),
            "gc_max_ms": 1e3 * max((d for _, d in pauses), default=0.0),
            "cpu_throttled_periods": throttled[0],
            "cpu_throttled_ms": throttled[1] / 1e3,
        }
        print(json.dumps(row), flush=True)
        steady = row["last_tenth_p50_ms"] <= 2 * row["first_tenth_p50_ms"] + 5
        if row["p99_ms"] <= args.p99_limit_ms and steady:
            knee = rate
    srv.release()
    print(json.dumps({"knee_per_s": knee, "p99_limit_ms": args.p99_limit_ms}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
