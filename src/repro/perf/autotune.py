"""Block/tile autotuning for the Pallas sweep kernel.

`kernels/fcm_update.py` exposes its two block sizes — ``tile_n`` (rows
per grid step) and ``lane`` (the padding multiple for the C and d axes)
— as parameters; this module searches a small grid of both through the
shared timing harness and persists the best config per (platform,
shape-bucket) in the calibration file under ``"tiles"`` (same format /
invalidation / wipe story as the backend race — see the `repro.perf`
package docstring).

`repro.kernels.ops` consults `tuned_blocks` (a cached-only lookup:
memo → disk, NEVER a fresh search) for its default blocks, so an
explicitly-tuned machine runs the tuned config everywhere without any
call-site change, and an untuned machine keeps the hand-picked
defaults.  Run the search via `tune_sweep_blocks` (the `t13_roofline`
bench and `scripts/verify.sh perf` both do).

On real TPU hardware ``lane`` must stay at the 128 MXU width — the grid
only explores smaller lanes in interpret mode, where padding C=8 → 128
is pure wasted VPU work and smaller pads win big.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

from repro import obs

TILE_GRID = (512, 1024, 2048)
LANE_GRID_INTERPRET = (32, 128)
DEFAULT_BLOCKS = {"tile_n": 1024, "lane": 128}

# (platform, bucket_key) -> tuned cfg | None
_MEMO: Dict[Tuple[str, str], Optional[dict]] = {}

__all__ = ["TILE_GRID", "LANE_GRID_INTERPRET", "DEFAULT_BLOCKS",
           "tune_sweep_blocks", "tuned_blocks"]


def _interpret() -> bool:
    import jax
    return jax.default_backend() != "tpu"


def tune_sweep_blocks(shape: Optional[Tuple[int, int, int]] = None, *,
                      path: Optional[str] = None, m: float = 2.0,
                      tiles: Sequence[int] = TILE_GRID,
                      lanes: Optional[Sequence[int]] = None,
                      iters: int = 2, refresh: bool = False) -> dict:
    """Search the (tile_n × lane) grid for ``shape``'s bucket; persist
    and return the best config ``{"tile_n": ..., "lane": ...,
    "times_us": {...}}``.  Cached per bucket — a second call is a
    lookup unless ``refresh=True``."""
    import jax

    from repro.kernels.fcm_update import fcm_accumulate_pallas
    from .calibrate import (DEFAULT_SHAPE, bucket_key, load_calibration,
                            race_shape, shape_bucket, store_calibration)
    from .microbench import time_fn
    from .roofline import _race_data

    bucket = shape_bucket(*(shape if shape is not None else DEFAULT_SHAPE))
    key = bucket_key(bucket)
    if not refresh:
        hit = tuned_blocks(shape, path=path)
        if hit is not None:
            return hit

    interp = _interpret()
    if lanes is None:
        lanes = LANE_GRID_INTERPRET if interp else (128,)
    n, c, d = race_shape(bucket)
    x, w, v = _race_data(n, c, d)
    times: Dict[str, float] = {}
    best, best_t = None, float("inf")
    for tile in tiles:
        for lane in lanes:
            fn = jax.jit(
                lambda a, b, v0, _t=tile, _l=lane: fcm_accumulate_pallas(
                    a, b, v0, m, tile_n=_t, lane=_l, interpret=interp))
            try:
                t = time_fn(fn, x, w, v, iters=iters)
            except Exception as e:
                times[f"t{tile}_l{lane}"] = float("nan")
                del e
                continue
            times[f"t{tile}_l{lane}"] = round(t * 1e6, 1)
            if t < best_t:
                best, best_t = {"tile_n": tile, "lane": lane}, t
    if best is None:            # every grid point failed: keep defaults
        best = dict(DEFAULT_BLOCKS)
    cfg = {**best, "times_us": times, "tuned_shape": [n, c, d]}
    obs.event("perf.autotune.tuned", bucket=key, tile_n=best["tile_n"],
              lane=best["lane"], times_us=times)
    data = load_calibration(path)
    data["tiles"][key] = cfg
    store_calibration(data, path)
    _MEMO[(jax.default_backend(), key)] = cfg
    return cfg


def tuned_blocks(shape: Optional[Tuple[int, int, int]] = None, *,
                 path: Optional[str] = None) -> Optional[dict]:
    """Cached-only lookup of the tuned blocks for ``shape``'s bucket:
    in-process memo, then the calibration file.  Returns None when the
    bucket has never been tuned — callers keep their defaults.  Never
    launches a search (kernel call sites stay cheap and side-effect
    free)."""
    import jax

    from .calibrate import bucket_key, load_calibration, shape_bucket, \
        DEFAULT_SHAPE

    bucket = shape_bucket(*(shape if shape is not None else DEFAULT_SHAPE))
    # The file is keyed by platform (`calibrate._registry_key`); the memo
    # is too, so blocks tuned on one platform never reach another.
    key = (jax.default_backend(), bucket_key(bucket))
    if key in _MEMO:
        return _MEMO[key]
    cfg = load_calibration(path)["tiles"].get(key[1])
    _MEMO[key] = cfg
    return cfg
