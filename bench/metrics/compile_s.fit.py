"""Seconds per fit spent lowering, compiling and loading programs from
the compile cache inside the window (`CompileClock`), on the host clock."""


def read(run):
    fits = run.record.get("fits")
    return sum(f["compile_s"] for f in fits) / len(fits) if fits else None
