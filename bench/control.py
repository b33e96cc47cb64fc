"""Readings that set a cell's limits: the program's and the control's,
seed by seed, in one process.

    python bench/control.py --workload <cell> --seconds 5 \\
        --program-seeds 1,2,3 --control-seeds 4,5,6

For each program seed, the cell's own set-up and a short window at its
own load, then the numbers its check compares.  For each control seed,
the same with the control (`bench/harness/control.py`) in the program's
place; for each fault seed of a fit cell, with the fit's loops stopped
before their first step (a state returned unchanged), and for each half
seed with the fit given only the first half of the rows.  Each line of
standard output is one JSON object: ``side``, ``seed`` and the
readings.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                os.path.join(os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__))), "src")]


def readings(cell, seed: int, seconds: float, clock, rows=None) -> dict:
    """One seed of the cell, as its run makes it, up to the readings."""
    from bench.run import traffic_kind

    traffic = traffic_kind(cell.traffic["kind"])(
        cell.config, cell.traffic, seed, clock, rows=rows)
    rec = traffic.window(seconds)
    traffic.release()
    attempted, failed = traffic.counts(rec)
    return dict(traffic.readings(rec), attempted=attempted, failed=failed)


def with_control(kind: str, side: str):
    """Put the control (or, for ``side == "fault"``, the unchanged-state
    fault; for ``"half"``, half of the rows left out) in the program's
    place; returns the undo."""
    import dataclasses

    import repro.core
    import repro.serve
    from bench.harness.control import ControlScorer, control_fit

    saved = repro.core.bigfcm_fit, repro.serve.Scorer
    real = saved[0]
    if side == "fault":
        repro.core.bigfcm_fit = lambda x, cfg, **kw: real(
            x, dataclasses.replace(cfg, max_iter=0), **kw)
    elif side == "half":
        repro.core.bigfcm_fit = lambda x, cfg, **kw: real(
            x[: x.shape[0] // 2], cfg, **kw)
    elif kind == "fit":
        repro.core.bigfcm_fit = control_fit
    else:
        repro.serve.Scorer = ControlScorer

    def undo():
        repro.core.bigfcm_fit, repro.serve.Scorer = saved
    return undo


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--program-seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--half-seeds", default="")
    ap.add_argument("--rows", type=int, default=None,
                    help="override the configuration's rows (CPU trials)")
    args = ap.parse_args(argv)

    from bench.harness.cell import load_cell
    from bench.harness.clock import CompileClock
    from bench.run import require_chips
    from repro.launch.cache import enable_compile_cache

    cell = load_cell(args.workload)
    if args.rows is None:
        require_chips(cell.chips)
    enable_compile_cache()
    clock = CompileClock()
    seeds = {"program": args.program_seeds, "control": args.control_seeds,
             "fault": args.fault_seeds, "half": args.half_seeds}
    for side, text in seeds.items():
        undo = (with_control(cell.traffic["kind"], side)
                if side != "program" else (lambda: None))
        try:
            for s in (int(v) for v in text.split(",") if v):
                r = readings(cell, s, args.seconds, clock, args.rows)
                print(json.dumps(dict(side=side, seed=s, **r)), flush=True)
        finally:
            undo()
    return 0


if __name__ == "__main__":
    sys.exit(main())
