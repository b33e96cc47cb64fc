import functools
import os
import sys

import numpy as np

# NOTE: device count is deliberately NOT forced here — smoke tests and
# benches must see the host's real (1-device) topology.  Multi-device
# tests spawn subprocesses that set XLA_FLAGS before importing jax.
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def seeded_cases(gen, n=20):
    """Seeded random-case fallback for ``@given`` when `hypothesis` is
    not installed (it is absent in this container and pip installs are
    not allowed): decorate a one-argument property test and run it over
    ``n`` deterministic cases drawn from ``gen(rng)``.

    ``gen`` mirrors a hypothesis strategy as a plain function of a
    `numpy.random.Generator`; seeds are 0..n−1, so failures reproduce
    with ``gen(np.random.default_rng(seed))``.
    """
    def deco(test):
        @functools.wraps(test)
        def runner():
            for seed in range(n):
                case = gen(np.random.default_rng(seed))
                try:
                    test(case)
                except AssertionError as e:
                    raise AssertionError(
                        f"seeded fallback case failed (seed={seed}, "
                        f"regenerate with gen(np.random.default_rng("
                        f"{seed}))): {e}") from e
        # pytest resolves fixtures through __wrapped__'s signature; the
        # case argument is supplied here, not by a fixture
        del runner.__wrapped__
        return runner
    return deco


def host_profile(run, trace_dir):
    """Run ``run()`` under the JAX profiler (CPU here) and return the
    host plane's events as ``(line, name, start_ns, dur_ns)``: a line
    (numbered) is one thread.  The profiler runs, and the trace is read
    back, as the chip benchmark does it: no Python tracer,
    `jax.profiler.ProfileData`."""
    import glob

    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        run()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    return [(i, e.name, e.start_ns, e.duration_ns)
            for plane in pd.planes if plane.name == "/host:CPU"
            for i, line in enumerate(plane.lines) for e in line.events]
