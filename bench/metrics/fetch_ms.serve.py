"""Mean milliseconds of the ``serve.fetch`` span in the traced window: the
wait for the device and the copy of the answers back."""
from bench.harness.spans import mean_ms


def read(run):
    return mean_ms(run.trace, "serve.fetch")
