"""Reducer iterations per fit (mean over fits), from the fit's
diagnostics."""


def read(run):
    fits = run.record.get("fits")
    return (sum(f["reducer_iters"] for f in fits) / len(fits)
            if fits else None)
