"""Mean milliseconds of the ``serve.launch`` span in the traced window: the
jitted scorer call, up to its return, its result not awaited."""
from bench.harness.spans import mean_ms


def read(run):
    return mean_ms(run.trace, "serve.launch")
