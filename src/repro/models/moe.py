"""Mixture-of-Experts layer with expert parallelism over the `model` axis.

Dispatch is sort-based with a capacity bound — gathers and scatters, NOT
one-hot einsums, so `cost_analysis` FLOPs stay ≈ the useful
6·T·k·D·F instead of being inflated by E/k (48× for kimi-k2).

Layout: entering the layer, activations are batch-sharded over
(pod, data) and replicated over `model` (the TP invariant after the
attention all-reduce).  Each model-rank owns E/|model| experts, selects
its own tokens (≤ capacity each) from its full local token slab, runs the
expert FFNs as one batched matmul, scatters weighted outputs back, and a
psum over `model` combines the top-k partial sums — the same collective
TP already pays for its FFN, so EP adds no extra collective step.

BigFCM tie-in: `repro.integration.router_init` seeds `w_router` with FCM
centroids of token embeddings.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.sharding.rules import data_axes, get_mesh, get_profile
from .params import PDecl


def moe_decl(cfg):
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    decl = {
        "w_router": PDecl((d, e), ("embed", None)),
        "w_in": PDecl((e, d, 2 * f),
                      ("experts", "expert_embed", "expert_mlp")),
        "w_out": PDecl((e, f, d),
                       ("experts", "expert_mlp", "expert_embed")),
    }
    if cfg.n_shared_experts:
        fs = f * cfg.n_shared_experts
        decl["w_shared_in"] = PDecl((d, 2 * fs), ("embed", "mlp"))
        decl["w_shared_out"] = PDecl((fs, d), ("mlp", "embed"))
    return decl


def _expert_ffn(w_in, w_out, x):
    """x: (E_loc, Cap, D) → (E_loc, Cap, D); SwiGLU experts."""
    h = jnp.einsum("ecd,edf->ecf", x, w_in.astype(x.dtype))
    u, g = jnp.split(h, 2, axis=-1)
    h = u * jax.nn.silu(g)
    return jnp.einsum("ecf,efd->ecd", h, w_out.astype(x.dtype))


def _moe_a2a(x, w_router, w_in, w_out, *, cfg, n_ranks: int,
             axis_name: str):
    """GShard-style expert parallelism with all-to-all dispatch
    (§Perf iteration for MoE): tokens are SHARDED over `model` (fsdp
    profile), so instead of replicating the token slab and psumming the
    full (T, D) output over `model` (2·T·D per layer), each rank routes
    its own tokens to the ranks owning their experts (≤ k·cf·T_loc·D
    moved, twice).  For kimi-k2 this is ~8× fewer bytes per MoE layer.

    x: (B_loc, S, D) this rank's tokens; w_in/w_out: (E_loc, ...)."""
    b, s, d = x.shape
    t = b * s
    e = cfg.n_experts
    e_loc = e // n_ranks
    k = cfg.top_k

    xt = x.reshape(t, d)
    logits = jnp.einsum("td,de->te", xt, w_router.astype(x.dtype))
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gate, eidx = jax.lax.top_k(probs, k)                  # (T, k)
    gate = gate / jnp.sum(gate, axis=-1, keepdims=True)

    # capacity per (expert, source-rank): every rank contributes ≤ cap
    cap = max(4, int(t * k * cfg.capacity_factor) // e)
    flat_e = eidx.reshape(-1)                             # (T·k,) global ids
    flat_g = gate.reshape(-1)
    tok = jnp.arange(t * k, dtype=jnp.int32) // k

    # pack into (E, cap, D) send buffer ordered by destination expert
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    counts = jax.ops.segment_sum(jnp.ones_like(sorted_e), sorted_e,
                                 num_segments=e)
    starts = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                              jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(t * k) - starts[sorted_e]
    valid = pos < cap
    slot = jnp.where(valid, sorted_e * cap + pos, e * cap)

    buf = jnp.zeros((e * cap, d), x.dtype)
    buf = buf.at[slot].set(xt[tok[order]], mode="drop")
    # (n_ranks, e_loc·cap, D) → a2a → rows from every source rank
    buf = buf.reshape(n_ranks, e_loc * cap, d)
    recv = jax.lax.all_to_all(buf, axis_name, split_axis=0, concat_axis=0,
                              tiled=False)
    # recv: (n_ranks, e_loc·cap, D): source-major; group by local expert
    recv = recv.reshape(n_ranks, e_loc, cap, d).transpose(1, 0, 2, 3) \
        .reshape(e_loc, n_ranks * cap, d)
    y = _expert_ffn(w_in, w_out, recv)
    # inverse permutation back to (n_ranks, e_loc·cap, D) and a2a home
    y = y.reshape(e_loc, n_ranks, cap, d).transpose(1, 0, 2, 3) \
        .reshape(n_ranks, e_loc * cap, d)
    back = jax.lax.all_to_all(y, axis_name, split_axis=0, concat_axis=0,
                              tiled=False)
    back = back.reshape(e * cap, d)

    gathered = back.at[slot].get(mode="fill", fill_value=0.0)
    w = jnp.where(valid, flat_g[order], 0.0).astype(x.dtype)
    out = jnp.zeros((t, d), x.dtype)
    out = out.at[tok[order]].add(gathered * w[:, None], mode="drop")
    return out.reshape(b, s, d)


def _moe_local(x, w_router, w_in, w_out, *, cfg, n_ranks: int,
               axis_name: Optional[str]):
    """Per-rank body.  x: (B_loc, S, D) replicated over `model`;
    w_in/w_out: (E_loc, ...) this rank's expert shard."""
    b, s, d = x.shape
    t = b * s
    e = cfg.n_experts
    e_loc = e // n_ranks
    k = cfg.top_k
    rank = (jax.lax.axis_index(axis_name) if axis_name else 0)
    my_lo = rank * e_loc

    xt = x.reshape(t, d)
    logits = jnp.einsum("td,de->te", xt, w_router.astype(x.dtype))
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gate, eidx = jax.lax.top_k(probs, k)                  # (T, k)
    gate = gate / jnp.sum(gate, axis=-1, keepdims=True)   # renormalize top-k

    cap = max(8, int(t * k * cfg.capacity_factor) // e)
    flat_e = eidx.reshape(-1)                             # (T·k,)
    flat_g = gate.reshape(-1)
    tok = jnp.arange(t * k, dtype=jnp.int32) // k

    mine = (flat_e >= my_lo) & (flat_e < my_lo + e_loc)
    local_e = jnp.where(mine, flat_e - my_lo, e_loc)      # e_loc = trash
    order = jnp.argsort(local_e, stable=True)             # (T·k,)
    sorted_e = local_e[order]
    counts = jax.ops.segment_sum(jnp.ones_like(sorted_e), sorted_e,
                                 num_segments=e_loc + 1)
    starts = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                              jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(t * k) - starts[sorted_e]            # rank within expert
    valid = (sorted_e < e_loc) & (pos < cap)
    slot = jnp.where(valid, sorted_e * cap + pos, e_loc * cap)

    buf = jnp.zeros((e_loc * cap, d), x.dtype)
    buf = buf.at[slot].set(xt[tok[order]], mode="drop")
    y_buf = _expert_ffn(w_in, w_out, buf.reshape(e_loc, cap, d))
    y_buf = y_buf.reshape(e_loc * cap, d)

    gathered = y_buf.at[slot].get(mode="fill", fill_value=0.0)
    w = jnp.where(valid, flat_g[order], 0.0).astype(x.dtype)
    out = jnp.zeros((t, d), x.dtype)
    out = out.at[tok[order]].add(gathered * w[:, None], mode="drop")
    if axis_name:
        out = jax.lax.psum(out, axis_name)
    return out.reshape(b, s, d)


def moe(cfg, p, x):
    """MoE FFN.  Uses shard_map EP when a mesh with a model axis is set.

    Two distributed modes:
      * tp profile — tokens replicated over `model`; each rank runs its
        expert shard over the full slab and a psum combines (no a2a, but
        2·T·D all-reduced per layer).
      * fsdp profile — tokens sharded over `model`; GShard all-to-all
        dispatch moves only routed tokens (§Perf hillclimb, kimi cell).
    """
    mesh = get_mesh()
    if mesh is not None and "model" in mesh.axis_names \
            and cfg.n_experts % mesh.shape["model"] == 0 \
            and mesh.shape["model"] > 1:
        n_ranks = mesh.shape["model"]
        daxes = data_axes(mesh)
        batch_axes = daxes + ("model",)
        a2a = (get_profile() == "fsdp"
               and x.shape[0] % (n_ranks * math.prod(
                   mesh.shape[a] for a in daxes)) == 0)
        if a2a:
            body = functools.partial(_moe_a2a, cfg=cfg, n_ranks=n_ranks,
                                     axis_name="model")
            x_spec = P(batch_axes, None, None)
        else:
            body = functools.partial(_moe_local, cfg=cfg, n_ranks=n_ranks,
                                     axis_name="model")
            x_spec = P(daxes, None, None)
        y = shard_map(
            body, mesh=mesh,
            in_specs=(x_spec, P(None, None),
                      P("model", None, None), P("model", None, None)),
            out_specs=x_spec,
            check_vma=False,
        )(x, p["w_router"], p["w_in"], p["w_out"])
    else:
        y = _moe_local(x, p["w_router"], p["w_in"], p["w_out"],
                       cfg=cfg, n_ranks=1, axis_name=None)

    if cfg.n_shared_experts:
        h = jnp.einsum("bsd,df->bsf", x, p["w_shared_in"].astype(x.dtype))
        u, g = jnp.split(h, 2, axis=-1)
        y = y + jnp.einsum("bsf,fd->bsd", u * jax.nn.silu(g),
                           p["w_shared_out"].astype(x.dtype))
    return y


def router_load(cfg, p, x):
    """Expert load histogram (for tests / router-init validation)."""
    logits = jnp.einsum("bsd,de->bse", x, p["w_router"].astype(x.dtype))
    _, eidx = jax.lax.top_k(jax.nn.softmax(logits, -1), cfg.top_k)
    return jnp.bincount(eidx.reshape(-1), length=cfg.n_experts)
