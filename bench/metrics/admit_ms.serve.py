"""Mean milliseconds of the ``serve.admit`` span in the traced window: a
submit waiting at the service's door for queue room."""
from bench.harness.spans import mean_ms


def read(run):
    return mean_ms(run.trace, "serve.admit")
