"""Pallas TPU kernel for the FCM accumulation sweep (paper Alg. 1 body).

TPU-native design (not a CUDA port — the paper has no GPU kernel; this is
the combiner hot loop re-thought for the TPU memory hierarchy):

  * The record stream X (N×d) is tiled over a 1-D grid; each grid step
    streams one (TILE_N × d_pad) block HBM→VMEM.
  * The center matrix V (C×d) is small (C ≤ ~512) and lives entirely in
    VMEM for the whole sweep — the TPU analogue of the Hadoop distributed
    cache file sitting next to every combiner.
  * Per tile, two MXU matmuls do all the heavy lifting:
       cross  = X · Vᵀ               (TILE_N × C)
       v_num += (w·u^m)ᵀ · X         (C × d)
    plus VPU elementwise work for the membership terms.  The N×C
    membership matrix exists only tile-wise in VMEM and never touches HBM
    — the Kolen–Hutcheson O(n·c) property, enforced architecturally.
  * C and d are zero-padded to multiples of 128 (MXU lane width); phantom
    centers are masked out of the membership denominator, phantom rows
    carry weight 0.
  * The record weights ride in the padded X block, in lane ``d`` (the
    first padding lane), so there is no separate (N, 1) weight array:
    in HBM such an array fills a 128-lane tile per row, as many bytes
    as X itself.  V is zero in that lane, so it adds nothing to x·vᵀ.
  * The three outputs (center numerators C×d, center masses C, objective)
    map every grid step to the same output block and accumulate across
    steps (revisited-block accumulation).

Roofline: per tile the kernel moves TILE_N·d·4 bytes and computes
2·TILE_N·C·d FLOPs twice ⇒ arithmetic intensity ≈ C FLOP/byte.  For
C ≥ 256 the sweep is compute-bound on v5e (197e12/819e9 ≈ 240).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_D2_FLOOR = 1e-12
LANE = 128
# Both MXU contractions run at full f32 precision: d² = x² + v² − 2·x·vᵀ
# cancels, and the kernel must agree with the f32 reference sweep.
_F32 = jax.lax.Precision.HIGHEST


def _fcm_tile_kernel(x_ref, v_ref, vnum_ref, wacc_ref, q_ref,
                     *, m: float, n_centers: int, dim: int):
    """One grid step: accumulate a TILE_N slab of records (features in
    lanes ``[0, dim)``, the record weight in lane ``dim``)."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        vnum_ref[...] = jnp.zeros_like(vnum_ref)
        wacc_ref[...] = jnp.zeros_like(wacc_ref)
        q_ref[...] = jnp.zeros_like(q_ref)

    xw = x_ref[...].astype(jnp.float32)           # (TN, dp)
    v = v_ref[...].astype(jnp.float32)            # (Cp, dp)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, xw.shape[1]), 1)
    w = jnp.sum(jnp.where(lane == dim, xw, 0.0), axis=-1,
                keepdims=True)                    # (TN, 1)
    x = jnp.where(lane < dim, xw, 0.0)            # (TN, dp)

    # ‖x−v‖² via the MXU: x² + v² − 2·x·vᵀ
    x2 = jnp.sum(x * x, axis=-1, keepdims=True)               # (TN, 1)
    v2 = jnp.sum(v * v, axis=-1)[None, :]                     # (1, Cp)
    cross = jax.lax.dot_general(
        x, v, (((1,), (1,)), ((), ())), precision=_F32,
        preferred_element_type=jnp.float32)                   # (TN, Cp) MXU
    d2 = jnp.maximum(x2 + v2 - 2.0 * cross, _D2_FLOOR)

    # membership terms, masking phantom (padded) centers out of the
    # normalizing denominator
    cp = v.shape[0]
    valid = (jax.lax.broadcasted_iota(jnp.int32, (1, cp), 1)
             < n_centers)                                      # (1, Cp)
    # log-space max-normalized membership (matches core.fcm._um_from_d2)
    expo = 1.0 / (m - 1.0)
    logd = jnp.where(valid, jnp.log(d2), jnp.inf)
    lmin = jnp.min(logd, axis=-1, keepdims=True)               # (TN, 1)
    r = jnp.where(valid, jnp.exp(-expo * (logd - lmin)), 0.0)
    u = r / jnp.sum(r, axis=-1, keepdims=True)
    um = jnp.power(u, m)                                       # u^m
    wum = um * w                                               # (TN, Cp)

    # accumulate: V numerators (MXU), center masses, objective
    vnum_ref[...] += jax.lax.dot_general(
        wum, x, (((0,), (0,)), ((), ())), precision=_F32,
        preferred_element_type=jnp.float32)                    # (Cp, dp)
    wacc_ref[...] += jnp.sum(wum, axis=0, keepdims=True)       # (1, Cp)
    q_ref[...] += jnp.sum(wum * d2, keepdims=True).reshape(1, 1)


def _pad_to(a: int, mult: int) -> int:
    return -(-a // mult) * mult


@functools.partial(jax.jit,
                   static_argnames=("m", "tile_n", "lane", "interpret"))
def fcm_accumulate_pallas(x, w, centers, m: float = 2.0, *,
                          tile_n: int = 1024, lane: int = LANE,
                          interpret: bool = False):
    """Raw Alg.-1 accumulators — the *streaming* kernel entry point.

    Returns ``(v_num, w_i, q)`` WITHOUT the final normalization: the
    weighted center numerators (C, d), center masses (C,), and objective
    contribution ().  All three are plain sums over records, so partial
    results from successive chunks of a stream add elementwise —
    ``accumulate`` over chunks then normalize once equals one sweep over
    the concatenation up to float32 summation order
    (`repro.kernels.ops.accumulate_chunks`).

    The two block sizes are tunable (`repro.perf.autotune` searches
    them): ``tile_n`` rows stream per grid step, and ``lane`` is the
    padding multiple for the C and d axes.  On real TPU hardware
    ``lane`` must stay at the 128 MXU width; interpret mode accepts
    smaller lanes, where not padding C=8 → 128 is a large win.

    x: (N, d) float32/bf16;  w: (N,);  centers: (C, d).
    """
    n, d = x.shape
    c = centers.shape[0]
    dp = _pad_to(d + 1, lane)         # lane d carries the record weight
    cp = _pad_to(max(c, lane), lane)
    tn = min(tile_n, _pad_to(n, 8))
    np_ = _pad_to(n, tn)

    # one fused concatenate+pad: no (N, 1) intermediate reaches HBM
    xf = jnp.pad(jnp.concatenate([x.astype(jnp.float32),
                                  w.astype(jnp.float32)[:, None]], axis=1),
                 ((0, np_ - n), (0, dp - d - 1)))
    vf = jnp.zeros((cp, dp), jnp.float32).at[:c, :d].set(
        centers.astype(jnp.float32))

    grid = (np_ // tn,)
    kernel = functools.partial(_fcm_tile_kernel, m=m, n_centers=c, dim=d)
    vnum, wacc, q = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tn, dp), lambda i: (i, 0)),   # X, w streamed
            pl.BlockSpec((cp, dp), lambda i: (0, 0)),   # V resident
        ],
        out_specs=[
            pl.BlockSpec((cp, dp), lambda i: (0, 0)),   # accumulated
            pl.BlockSpec((1, cp), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((cp, dp), jnp.float32),
            jax.ShapeDtypeStruct((1, cp), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(xf, vf)

    return vnum[:c, :d], wacc[0, :c], q[0, 0]


@functools.partial(jax.jit,
                   static_argnames=("m", "tile_n", "lane", "interpret"))
def fcm_sweep_pallas(x, w, centers, m: float = 2.0, *,
                     tile_n: int = 1024, lane: int = LANE,
                     interpret: bool = False):
    """Pallas-backed Alg.-1 sweep.  Returns (v_new, w_i, q) like
    ``core.fcm.fcm_sweep``: the accumulate entry point plus the one
    normalization it defers."""
    v_num, w_i, q = fcm_accumulate_pallas(x, w, centers, m, tile_n=tile_n,
                                          lane=lane, interpret=interpret)
    v_new = v_num / jnp.maximum(w_i, _D2_FLOOR)[:, None]
    return v_new, w_i, q
