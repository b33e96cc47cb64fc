"""JAX's persistent compilation cache for the entry points.

`chip_smoke.py`, `benchmarks/run.py` and the examples call
`enable_compile_cache` once before their first compile; no library
module calls it, so importing the package changes no JAX setting.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads the cache
from there and nothing here overrides it.  Otherwise the cache goes to
``<repo>/.cache/jax``, built from this file's place in the checkout.
The path is part of what makes a cache hit possible, so it never comes
from the working directory, a temporary name, a process id or the time.
"""
from __future__ import annotations

import os

import jax

ENV_DIR = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                         "..", "..", ".."))


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Every compile is cached (no minimum compile time): a cold machine
    pays for a program once per cache directory."""
    path = os.environ.get(ENV_DIR)
    if not path:
        path = os.path.join(REPO_ROOT, ".cache", "jax")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
