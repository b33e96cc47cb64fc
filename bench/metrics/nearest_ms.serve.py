"""Device milliseconds per scoring batch in the traced window: the
device's busy time over the window's ``serve.batches``.  It reads the
same work whatever implements the assignment."""
from bench.harness.trace import mean_busy_s


def read(run):
    obs = run.record.get("obs")
    if not obs or not obs["batches"] or not run.trace.devices:
        return None
    return 1e3 * mean_busy_s(run.trace) / obs["batches"]
