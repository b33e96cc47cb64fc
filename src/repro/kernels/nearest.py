"""Pallas TPU kernel for nearest-center assignment at large C.

`engine.hard_assign` is ``argmin(pairwise_sqdist(x, v))``: it writes the
whole (N, C) distance matrix to HBM and reads it back.  At C = 65,536
centers (an IVF coarse quantizer) and a 4,096-row batch that matrix is
1.07 GB, and its round trip costs more than the distances themselves.
This kernel never forms it:

  * Grid (row tiles, C tiles), the C axis last and sequential.  A row
    tile of X (TN × d_pad) stays in VMEM while V streams through in
    (TC × d_pad) tiles; V is read once per row tile.
  * Each step computes the cross term on the MXU at ``HIGHEST``,
    transposed, V·Xᵀ (TC × TN), so that rows run along the 128 lanes
    and the reduction over centers runs over sublanes and vregs: the
    answers come out lane-dense, one int32 per row.
  * d² is formed as `engine.pairwise_sqdist` forms it, x² + v² − 2·cross
    floored at ``_D2_FLOOR``, with x² computed outside the kernel and v²
    per V tile.  Padded centers get v² = +inf, so they can never win;
    padded rows are sliced off.
  * A running (min, argmin) per row lives in VMEM scratch, 8 sublanes
    deep: each step folds its TC centers into the 8 sublanes with
    element-wise min (no cross-lane work), and the last C step folds
    the 8 sublanes into one answer per row.  Ties go to the lowest
    center index, as `jnp.argmin` gives.

Work per step: 2·TC·TN·d multiply-adds on the MXU (six bf16 passes at
``HIGHEST``) against about eight VPU operations per (center, row) pair,
and TC·d·4 bytes of V from HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .fcm_update import _D2_FLOOR, _F32, LANE, _pad_to

# 2,048 x 512: within 1.3 % of the least device time of four tilings at
# 4,096 rows and C = 256-65,536 on a v5e, 2.5-3.1 % under 1,024 x 512
# (PERF.md §6, the C sweep)
TILE_N = 2048       # rows per tile: V is read N / TILE_N times a batch
TILE_C = 512        # centers per step: (TILE_C, TILE_N) f32 = 4 MB in VMEM
SUBLANES = 8


def _nearest_tile_kernel(x2_ref, x_ref, v_ref, out_ref, dmin_ref, imin_ref,
                         *, n_centers: int):
    """One grid step: the nearest of ``v_ref``'s centers for each row of
    ``x_ref``, folded into the running (min, argmin) of the row tile."""
    j = pl.program_id(1)
    tc, tn = v_ref.shape[0], x_ref.shape[0]

    @pl.when(j == 0)
    def _init():
        dmin_ref[...] = jnp.full(dmin_ref.shape, jnp.inf, jnp.float32)
        imin_ref[...] = jnp.zeros(imin_ref.shape, jnp.int32)

    v = v_ref[...]                                              # (TC, dp)
    first = j * tc
    center = first + jax.lax.broadcasted_iota(jnp.int32, (tc, 1), 0)
    v2 = jnp.where(center < n_centers,
                   jnp.sum(v * v, axis=-1, keepdims=True), jnp.inf)
    cross = jax.lax.dot_general(
        v, x_ref[...], (((1,), (1,)), ((), ())), precision=_F32,
        preferred_element_type=jnp.float32)                     # (TC, TN)
    d2 = jnp.maximum(x2_ref[...] + v2 - 2.0 * cross, _D2_FLOOR)

    # fold the TC centers into 8 sublanes: group g holds centers
    # first + 8·g + s in sublane s
    d2 = d2.reshape(tc // SUBLANES, SUBLANES, tn)
    low = jnp.min(d2, axis=0)                                   # (8, TN)
    group = jax.lax.broadcasted_iota(jnp.int32, d2.shape, 0)
    g = jnp.min(jnp.where(d2 == low[None], group, tc), axis=0)
    idx = (first + SUBLANES * g
           + jax.lax.broadcasted_iota(jnp.int32, low.shape, 0))
    better = low < dmin_ref[...]       # strict: earlier tiles keep ties
    dmin_ref[...] = jnp.where(better, low, dmin_ref[...])
    imin_ref[...] = jnp.where(better, idx, imin_ref[...])

    @pl.when(j == pl.num_programs(1) - 1)
    def _answer():
        dmin, imin = dmin_ref[...], imin_ref[...]
        best = jnp.min(dmin, axis=0, keepdims=True)             # (1, TN)
        out_ref[...] = jnp.min(jnp.where(dmin == best, imin, n_centers),
                               axis=0, keepdims=True)


@functools.partial(jax.jit,
                   static_argnames=("tile_n", "tile_c", "interpret"))
def nearest_center_pallas(x, centers, *, tile_n: int = TILE_N,
                          tile_c: int = TILE_C, interpret: bool = False):
    """The index of each row's nearest center: ``(N,) int32``, equal to
    ``argmin(pairwise_sqdist(x, centers), axis=-1)``.

    x: (N, d);  centers: (C, d).  Rows pad to a multiple of the row
    tile, C to a multiple of the C tile, d to the 128-lane width."""
    n, d = x.shape
    c = centers.shape[0]
    x = x.astype(jnp.float32)
    centers = centers.astype(jnp.float32)
    dp = _pad_to(d, LANE)
    tn = min(tile_n, _pad_to(n, SUBLANES))
    np_ = _pad_to(n, tn)
    tc = min(tile_c, _pad_to(c, SUBLANES))
    cp = _pad_to(c, tc)

    x2 = jnp.pad(jnp.sum(x * x, axis=-1), (0, np_ - n))[None, :]
    xp = jnp.pad(x, ((0, np_ - n), (0, dp - d)))
    vp = jnp.pad(centers, ((0, cp - c), (0, dp - d)))

    kernel = functools.partial(_nearest_tile_kernel, n_centers=c)
    out = pl.pallas_call(
        kernel,
        grid=(np_ // tn, cp // tc),
        in_specs=[
            pl.BlockSpec((1, tn), lambda i, j: (0, i)),     # x², lane-dense
            pl.BlockSpec((tn, dp), lambda i, j: (i, 0)),    # X tile, held
            pl.BlockSpec((tc, dp), lambda i, j: (j, 0)),    # V streamed
        ],
        out_specs=pl.BlockSpec((1, tn), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, np_), jnp.int32),
        scratch_shapes=[pltpu.VMEM((SUBLANES, tn), jnp.float32),
                        pltpu.VMEM((SUBLANES, tn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x2, xp, vp)
    return out[0, :n]
