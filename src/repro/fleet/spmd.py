"""SPMD summary exchange — the fleet reduction as one mesh collective.

When the "hosts" are devices of one jax mesh (a real multi-host SPMD
job, or a forced-multi-device simulation via
``--xla_force_host_platform_device_count``), the transport layer
disappears entirely: the exchange is an ``all_gather`` of the per-host
summary inside `jax.shard_map` followed by the same pairwise merge — run
replicated on every device, exactly as `FleetHost.exchange` runs it on
every process.

Quantized exchange is the `repro.train.dp` compressed-collective idiom:
cast to the wire dtype BEFORE the gather (bf16 halves the bytes the
interconnect moves — the cast is the compression), upcast to float32
after.  `repro.fleet.wire.BF16_REL_BOUND` bounds the per-element error
identically in both articles, since both quantize once with
round-to-nearest.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.engine import MergePlan, Summary, merge_summaries


def mesh_exchange(
    stacked: Summary,
    mesh: Mesh,
    *,
    axis: str = "data",
    plan: Optional[MergePlan] = None,
    wire_dtype=None,
    backend=None,
) -> Summary:
    """Merge per-device summaries into one replicated global summary.

    ``stacked`` is the (H, C, d)/(H, C) stack whose leading axis is (or
    will be) sharded over ``axis`` — one summary per mesh position.
    ``wire_dtype`` (e.g. ``jnp.bfloat16``) quantizes the gather's wire
    format.  Returns the merged (C, d)/(C,) summary, identical on every
    device."""
    plan = plan or MergePlan("pairwise")
    if plan.topology != "pairwise":
        raise ValueError("mesh_exchange runs the fleet reduction — a "
                         f"pairwise plan — got {plan.topology!r}")

    def body(cs, ms):
        c, w = cs[0], ms[0]              # my (C, d)/(C,) slice
        if wire_dtype is not None:
            c = c.astype(wire_dtype)     # compression IS the cast:
            w = w.astype(wire_dtype)     # bytes shrink before the wire
        gc = jax.lax.all_gather(c, axis).astype(jnp.float32)
        gw = jax.lax.all_gather(w, axis).astype(jnp.float32)
        res = merge_summaries(Summary(gc, gw), plan, backend=backend)
        return res.summary.centers, res.summary.masses

    f = shard_map(body, mesh=mesh,
                  in_specs=(P(axis), P(axis)),
                  out_specs=(P(None, None), P(None)),
                  check_vma=False)
    centers, masses = jax.jit(f)(jnp.asarray(stacked.centers, jnp.float32),
                                 jnp.asarray(stacked.masses, jnp.float32))
    return Summary(centers, masses)
