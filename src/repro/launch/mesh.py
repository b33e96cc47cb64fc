"""Production mesh construction.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before any device query).

Every axis is `AxisType.Auto`: the code places data with
`NamedSharding` and `shard_map` and leaves the rest to the compiler's
sharding propagation.  `Explicit` axes (`jax.make_mesh`'s default)
reject the implicit gathers that code relies on.
"""
from __future__ import annotations

import math

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """v5e pod mesh: 16×16 (data, model) per pod; ×2 pods multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, have {len(devices)} — run "
            "under launch/dryrun.py which forces 512 host devices")
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes),
                         devices=devices[:n])


def make_host_mesh(model_parallel: int = 1):
    """Small mesh over whatever the host actually has (tests/examples)."""
    devices = jax.devices()
    n = len(devices)
    mp = math.gcd(model_parallel, n)
    return jax.make_mesh((n // mp, mp), ("data", "model"),
                         (AxisType.Auto, AxisType.Auto), devices=devices)
