"""`repro.perf` — the measured performance plane (PR 6).

Every speed decision the engine makes is empirical, not faith:

  * **microbench** — ERT-style peak probes (streaming-bandwidth triad,
    matmul-FLOPs kernel) and the shared `time_fn` harness every other
    perf module times through.
  * **roofline** — the unified roofline layer: the per-kernel analytic
    bytes/FLOPs model for the O(n·c) accumulation sweep
    (`sweep_flops`/`sweep_bytes`), achieved-vs-peak measurement per
    (backend, shape-bucket) (`kernel_roofline`/`roofline_report`), and
    the compiled-program roofline terms (Roofline dataclass +
    trip-count-corrected HLO collective parse) that
    `repro.launch.roofline` re-exports for the dry-run path.
  * **calibrate** — the calibration cache behind
    ``resolve_backend("auto")``: a one-shot timed race of every
    registered sweep backend per (platform, shape-bucket), winner
    persisted on disk; the platform-name rule is a fallback only.
  * **autotune** — block/tile-size search for the Pallas sweep kernel
    (`tile_n` × `lane`), best config persisted in the same cache and
    picked up by `repro.kernels.ops` as the kernel's default blocks.

Calibration-file format
-----------------------
One JSON file (default ``$REPRO_CALIB_DIR/calibration.json``, else
``./.cache/perf/calibration.json`` under the current working
directory), written atomically (tmp + rename, manifest-style like
`repro.data.cache.ChunkStore`):

    {
      "key": {"format_version": 1, "platform": "cpu",
              "jax": "0.9.0", "backends": ["jnp", "jnp_bf16", ...]},
      "winners": {"n4096_c8_d16": {"winner": "jnp",
                                   "times_us": {...}, "parity": {...},
                                   "raced_shape": [4096, 8, 16]}},
      "tiles":   {"n4096_c8_d16": {"tile_n": 1024, "lane": 128,
                                   "times_us": {...}}},
      "peaks":   {"stream_bytes_per_s": ..., "matmul_f32_flops_per_s":
                  ..., "matmul_bf16_flops_per_s": ...}
    }

The ``key`` block is the content key: a file whose key does not match
the current process (different platform, jax version, or
registered-backend set) is discarded wholesale and re-raced — that is
the invalidation rule, there is no per-entry TTL.  A corrupt or
truncated file is treated as absent (fresh race), never an error.

Shape-bucket rule
-----------------
``shape_bucket(n, c, d)`` rounds every dimension up to the next power
of two (n clamped to [256, 2**20]); one race/tuning result serves every
shape in its bucket.  Races run at the bucket's representative shape
with n capped at 4096 rows so a cold first call stays sub-second-ish
even on interpret-mode backends.

Wiping / refreshing
-------------------
``repro.perf.calibrate.wipe()`` deletes the file and the in-process
memo; ``calibrated_backend_name(..., refresh=True)`` re-races one
bucket in place.  Set ``REPRO_AUTO_CALIBRATE=0`` to disable measured
selection entirely (``resolve_backend("auto")`` then falls back to the
platform-name rule); point ``REPRO_CALIB_DIR`` somewhere else to
sandbox the cache (tests do).
"""
from .autotune import tune_sweep_blocks, tuned_blocks
from .calibrate import (calibrated_backend_name, calibration_path,
                        clear_memory_cache, race_backends, shape_bucket,
                        wipe)
from .microbench import (probe_matmul_flops, probe_peaks,
                         probe_stream_bandwidth, time_fn)
from .roofline import (kernel_roofline, roofline_report, sweep_bytes,
                       sweep_flops, sweep_intensity)

__all__ = [
    "tune_sweep_blocks", "tuned_blocks",
    "calibrated_backend_name", "calibration_path", "clear_memory_cache",
    "race_backends", "shape_bucket", "wipe",
    "probe_matmul_flops", "probe_peaks", "probe_stream_bandwidth",
    "time_fn",
    "kernel_roofline", "roofline_report", "sweep_bytes", "sweep_flops",
    "sweep_intensity",
]
