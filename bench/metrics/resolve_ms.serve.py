"""Mean milliseconds of the ``serve.resolve`` span in the traced window:
building the responses and resolving their futures, the clients'
callbacks included."""
from bench.harness.spans import mean_ms


def read(run):
    return mean_ms(run.trace, "serve.resolve")
