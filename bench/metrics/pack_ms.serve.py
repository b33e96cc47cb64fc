"""Mean milliseconds of the ``serve.pack`` span in the traced window: the
worker concatenating a batch's requests and padding them to the bucket."""
from bench.harness.spans import mean_ms


def read(run):
    return mean_ms(run.trace, "serve.pack")
