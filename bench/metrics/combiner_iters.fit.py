"""Combiner iterations per fit (mean over fits of the mean over data
partitions), from the fit's diagnostics."""


def read(run):
    fits = run.record.get("fits")
    if not fits:
        return None
    return sum(sum(f["combiner_iters"]) / len(f["combiner_iters"])
               for f in fits) / len(fits)
