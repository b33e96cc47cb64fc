"""jit'd public wrappers for the Pallas kernels + backend registration.

This module is where the kernel layer plugs into `repro.engine`: importing
it registers the ``pallas`` (fused sweep) and ``pallas_accumulate`` (raw
accumulators, normalization deferred across chunks/slots) backends, which
is how every consumer reaches the kernels — through
``engine.resolve_backend``, never by importing sweeps ad hoc.  The
``pallas`` backend also owns hard assignment: at large C on a TPU it
runs the C-tiled nearest-center kernel (`nearest_by_kernel`, a rule on
the shape it sees, not a timed race).
``accumulate_chunks`` folds a chunk stream through the raw entry point —
one normalization at the end, exactly equal to a single sweep over the
concatenated records.  On CPU the kernel body runs in interpret mode; on
TPU it lowers to Mosaic.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.engine.backend import (SweepBackend, normalize_accumulators,
                                  on_tpu, register_backend)

from .fcm_update import (_D2_FLOOR, LANE, fcm_accumulate_pallas,
                         fcm_sweep_pallas)
from .nearest import nearest_center_pallas

# Hard assignment takes the C-tiled kernel from this many centers on,
# on a TPU only: the smallest C at which the kernel took less device
# time than the jnp path at both 256 and 4,096 rows, d = 128, in a
# chip sweep (`scripts/nearest_sweep.py`; table in PERF.md §6).
# At C = 256 and 256 rows the jnp path was faster; below 256 the sweep
# did not go.
NEAREST_MIN_CENTERS = 1024


def interpret_mode() -> bool:
    """Whether the kernels run in Pallas interpret mode: on the CPU,
    for parity testing.  Elsewhere Mosaic compiles them."""
    return jax.default_backend() == "cpu"


def _blocks_for(x, centers, tile_n, lane) -> dict:
    """Resolve the kernel's block sizes: explicit args win, otherwise
    the autotuned config for this shape bucket (`repro.perf.autotune`,
    cached-only — never triggers a search), otherwise the hand-picked
    defaults.  Runs at trace time only (static kernel params).  A broken
    perf layer raises on a TPU; elsewhere the defaults still work."""
    tuned = None
    if tile_n is None or lane is None:
        try:
            from repro.perf.autotune import tuned_blocks
            tuned = tuned_blocks((x.shape[0], centers.shape[0],
                                  centers.shape[1]))
        except Exception:
            if on_tpu():
                raise
            tuned = None
        tuned = tuned or {}
    return {"tile_n": tile_n if tile_n is not None
            else tuned.get("tile_n", 1024),
            "lane": lane if lane is not None else tuned.get("lane", LANE)}


def fcm_sweep_kernel(x, w, centers, m: float = 2.0, *,
                     tile_n: int = None, lane: int = None):
    """Fused Pallas sweep — drop-in for the jnp `engine.fcm_sweep`.
    Block sizes default to the autotuned config for this shape bucket
    when one exists (see `_blocks_for`)."""
    return fcm_sweep_pallas(x, w, centers, m, interpret=interpret_mode(),
                            **_blocks_for(x, centers, tile_n, lane))


def fcm_accumulate_kernel(x, w, centers, m: float = 2.0, *,
                          tile_n: int = None, lane: int = None):
    """Raw (v_num, w_i, q) accumulators for one record chunk."""
    return fcm_accumulate_pallas(x, w, centers, m,
                                 interpret=interpret_mode(),
                                 **_blocks_for(x, centers, tile_n, lane))


def accumulate_chunks(chunks, weights, centers, m: float = 2.0, *,
                      tile_n: int = None, accumulate_fn=None):
    """One FCM sweep over a stream of chunks without materializing it.

    ``chunks``/``weights`` are iterables of (n_i, d)/(n_i,) arrays —
    e.g. a `repro.data.stream` source.  Per chunk the kernel emits raw
    accumulators; they sum elementwise across chunks (every output is a
    plain record sum) and normalize once — matching a single sweep over
    the concatenation up to float32 summation order.  Returns
    (v_new, w_i, q) like the engine sweep.
    """
    acc = accumulate_fn or fcm_accumulate_kernel
    v_num, w_i, q = None, None, None
    for x, w in zip(chunks, weights, strict=True):
        vn, wi, qi = acc(x, w, centers, m, tile_n=tile_n)
        if v_num is None:
            v_num, w_i, q = vn, wi, qi
        else:
            v_num, w_i, q = v_num + vn, w_i + wi, q + qi
    if v_num is None:
        raise ValueError("accumulate_chunks: empty chunk stream")
    v_new = v_num / jnp.maximum(w_i, _D2_FLOOR)[:, None]
    return v_new, w_i, q


def nearest_by_kernel(n_centers: int, tpu: bool) -> bool:
    """The shape rule of hard assignment: the C-tiled kernel on a TPU
    at ``NEAREST_MIN_CENTERS`` centers or more, else the jnp path."""
    return tpu and n_centers >= NEAREST_MIN_CENTERS


# --------------------------------------------------- engine registration ---

class PallasBackend(SweepBackend):
    """Fused Pallas TPU sweep (interpret mode on CPU, for parity)."""

    name = "pallas"

    def accumulate(self, x, w, centers, m):
        return fcm_accumulate_kernel(x, w, centers, m)

    def sweep(self, x, w, centers, m):
        return fcm_sweep_kernel(x, w, centers, m)

    def assigns_by_kernel(self, n_centers: int) -> bool:
        return nearest_by_kernel(n_centers, on_tpu())

    def hard_assign(self, x, centers):
        if self.assigns_by_kernel(centers.shape[0]):
            return nearest_center_pallas(x, centers,
                                         interpret=interpret_mode())
        return super().hard_assign(x, centers)


class PallasAccumulateBackend(SweepBackend):
    """Raw-accumulator Pallas entry (`fcm_accumulate_pallas`): chunks,
    window slots, and shards sum their (v_num, w_i, q) partials and
    normalize ONCE — the streaming / fused-window-merge backend.

    Same kernel as `PallasBackend` — the two differ in *entry point*,
    not math: this one's sweep routes through the public accumulate
    wrapper + an out-of-kernel normalization, so a whole-sweep consumer
    and a chunked-accumulate consumer are bit-identical per chunk."""

    name = "pallas_accumulate"

    def accumulate(self, x, w, centers, m):
        return fcm_accumulate_kernel(x, w, centers, m)

    def sweep(self, x, w, centers, m):
        return normalize_accumulators(
            *fcm_accumulate_kernel(x, w, centers, m))


register_backend(PallasBackend())
register_backend(PallasAccumulateBackend())
