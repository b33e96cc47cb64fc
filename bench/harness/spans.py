"""Durations of one program span in a traced run's window.

Spans of `repro.obs` are profiler events on the host plane of the
trace, named by the bare span name; the profiler may append metadata
after a ``#`` (``name#k=v#``), which is cut off before the names are
compared.  A span counts where it starts inside the window.  The span
names live in the readers of ``bench/metrics/``, not in the program, so
a span the program renames reads as nothing, not as something else.
"""
from __future__ import annotations

from typing import List, Optional


def durations_ns(trace, name: str) -> List[float]:
    """The durations of the host events named ``name`` that start in
    the window of ``trace`` (a `bench.harness.trace.Trace`)."""
    lo, hi = trace.start_ns, trace.start_ns + trace.window_ns
    return [d for n, s, d in trace.host
            if lo <= s < hi and n.split("#", 1)[0] == name]


def mean_ms(trace, name: str) -> Optional[float]:
    """Mean milliseconds of the span ``name`` in the window; None where
    the window has none."""
    d = durations_ns(trace, name)
    return sum(d) / len(d) / 1e6 if d else None
