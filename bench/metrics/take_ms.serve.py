"""Mean milliseconds of the ``serve.take`` span in the traced window: the
worker waiting for requests and taking a batch of them."""
from bench.harness.spans import mean_ms


def read(run):
    return mean_ms(run.trace, "serve.take")
