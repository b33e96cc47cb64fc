"""Device milliseconds per call of the sweep kernel over a chip's whole
share of the data, from the trace."""
from bench.harness.sweep import full_sweeps


def read(run):
    calls = full_sweeps(run)
    return 1e-6 * sum(op.dur_ns for op in calls) / len(calls) \
        if calls else None
