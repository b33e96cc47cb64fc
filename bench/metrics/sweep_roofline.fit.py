"""The sweep kernel's share of its roofline, in %: the least time the
chip could take for one sweep over a chip's share of the data (the
larger of algorithmic FLOPs over peak FLOP/s and bytes over peak HBM
bytes/s, at the unpadded rows, clusters and features) over the mean
device time of such a call."""
from bench.harness.roofline import sweep_roofline
from bench.harness.sweep import chip_rows, full_sweeps


def read(run):
    calls = full_sweeps(run)
    if not calls:
        return None
    seconds = 1e-9 * sum(op.dur_ns for op in calls) / len(calls)
    cfg = run.cell.config
    share, _ = sweep_roofline(chip_rows(run), int(cfg["clusters"]),
                              int(cfg["features"]), seconds,
                              run.device_kind)
    return share
