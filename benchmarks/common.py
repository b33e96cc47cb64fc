"""Shared benchmark helpers.

The paper's absolute numbers come from a 2016 Hadoop cluster; this
harness validates the paper's *relative* claims on CPU-budget-scaled
record counts (documented per table in EXPERIMENTS.md).  Output format:
``name,us_per_call,derived`` CSV rows, plus a structured row dict per
emit (``ROWS_META``) tagged with platform/backend/interpret metadata —
cross-machine perf-trajectory comparisons filter on those fields, never
on free-text ``derived`` strings.
"""
from __future__ import annotations

import functools
import os
import subprocess
import time
from typing import Callable, Optional

import jax

ROWS = []
ROWS_META = []


@functools.lru_cache(maxsize=1)
def git_commit() -> str:
    """The repo's HEAD commit hash, best-effort: empty string outside a
    git checkout (or without git) — perf rows stay comparable across
    machines either way, but a hash pins a row to the exact code."""
    try:
        out = subprocess.run(
            ["git", "rev-parse", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True, timeout=10)
    except (OSError, subprocess.TimeoutExpired):
        return ""
    return out.stdout.strip() if out.returncode == 0 else ""


def on_interpret(backend_name: str) -> Optional[bool]:
    """Whether a named sweep backend runs its kernel in interpret mode
    on this host: True/False for the Pallas backends, None (not
    applicable) for the pure-jnp ones."""
    if not backend_name.startswith("pallas"):
        return None
    from repro.kernels.ops import interpret_mode
    return interpret_mode()


def emit(name: str, us_per_call: float, derived: str = "", *,
         backend: Optional[str] = None, interpret: Optional[bool] = None,
         **extra) -> dict:
    """Print/record one benchmark row.

    The CSV line keeps the historical 3-column format; the returned
    dict (also appended to ``ROWS_META``) carries the structured
    metadata — ``platform`` always, ``backend``/``interpret`` when the
    caller passes them (pass ``backend=`` whenever a row is
    backend-specific; ``interpret`` defaults from `on_interpret`).
    Benches that write a ``BENCH_*.json`` should store these dicts as
    their rows.
    """
    row = f"{name},{us_per_call:.1f},{derived}"
    ROWS.append(row)
    print(row, flush=True)
    meta = {"name": name, "us_per_call": round(us_per_call, 1),
            "derived": derived, "platform": jax.default_backend(),
            "git_commit": git_commit()}
    if backend is not None:
        meta["backend"] = backend
        if interpret is None:
            interpret = on_interpret(backend)
    if interpret is not None:
        meta["interpret"] = bool(interpret)
    meta.update(extra)
    ROWS_META.append(meta)
    return meta


def timeit(fn: Callable, *args, warmup: int = 1, iters: int = 3) -> float:
    """Median wall-time (seconds) with block_until_ready."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def wall(fn: Callable, warmup: int = 1) -> float:
    """Wall-time one call after `warmup` warm-up calls.  The per-iteration
    -job baselines (`baselines/mr_fkm.py`) exclude their XLA compile from
    timing ("warm JVM"); timing BigFCM cold would charge it ~5 graph
    compiles (~seconds on this 1-core CPU) that a deployed service pays
    once — warm-vs-warm is the apples-to-apples comparison."""
    for _ in range(warmup):
        fn()
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0
