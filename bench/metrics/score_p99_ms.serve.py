"""99th percentile, by nearest rank, of request latency from due time
to answer over every request of the window, in ms; a request refused or
never answered counts as infinite.  Host stalls of the whole process set
it (PERF.md), so it stands here, without a bound, beside the cell's
completed-records rate."""
from bench.kinds.serve import ANSWERED, nearest_rank


def read(run):
    rec = run.record
    if "status" not in rec:
        return None
    lat = rec["done"] - rec["due"]
    lat[rec["status"] != ANSWERED] = float("inf")
    return 1e3 * nearest_rank(lat, 0.99)
