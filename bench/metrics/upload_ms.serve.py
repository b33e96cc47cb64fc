"""Mean milliseconds of the ``serve.upload`` span in the traced window: the
host-to-device copy of a padded batch."""
from bench.harness.spans import mean_ms


def read(run):
    return mean_ms(run.trace, "serve.upload")
