"""Nearest-center assignment's share of its roofline, in %: the least
time the chip could take for the window's batches (each at the mean
rows a batch, ``serve.records`` over ``serve.batches``, against the
configuration's centers, by `nearest_flops` and `nearest_bytes`) over
the device's busy time in the window.  The work is FLOP-bound and the
peak is the bf16 one, so a float32 cross term at ``HIGHEST`` (six bf16
passes) tops out near a sixth of it."""
from bench.harness.nearest import nearest_least_s
from bench.harness.trace import mean_busy_s


def read(run):
    obs = run.record.get("obs")
    if not obs or not obs["batches"] or not run.trace.devices:
        return None
    busy = mean_busy_s(run.trace)
    if busy <= 0:
        return None
    cfg = run.cell.config
    rows = obs["records"] / obs["batches"]
    least = obs["batches"] * nearest_least_s(
        rows, int(cfg["clusters"]), int(cfg["features"]), run.device_kind)
    return 100.0 * least / busy
