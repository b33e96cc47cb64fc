"""Share of the window's scoring batches whose labels came from the
program's C-tiled nearest-center kernel, in %: ``serve.tiled`` over
``serve.batches``; None where the program counts no ``serve.tiled``."""


def read(run):
    obs = run.record.get("obs")
    if not obs or obs.get("tiled") is None or not obs["batches"]:
        return None
    return 100.0 * obs["tiled"] / obs["batches"]
