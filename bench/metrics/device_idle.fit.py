"""Share of the traced window in which no operation ran on the device,
in %, averaged over the chips used."""
from bench.harness.trace import idle_percent


def read(run):
    return idle_percent(run.trace)
