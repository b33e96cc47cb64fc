"""99th percentile of how late the load generator sent each request
(send time minus due time), in ms, on the host clock."""
import numpy as np

from bench.kinds.serve import nearest_rank


def read(run):
    rec = run.record
    if "sent" not in rec or not len(rec["sent"]):
        return None
    return 1e3 * nearest_rank(np.asarray(rec["sent"]) - rec["due"], 0.99)
