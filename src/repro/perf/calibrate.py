"""Calibrated backend auto-selection — the cache behind ``"auto"``.

``resolve_backend("auto")`` used to pick by platform name (TPU →
pallas, else jnp) — faith, not data: on this CPU the interpret-mode
pallas path loses to jnp by ~50× yet the rule couldn't know.  Now
"auto" asks `calibrated_backend_name`, which runs a **one-shot timed
race** of every registered sweep backend at the request's shape bucket,
persists the winner in the calibration file (format, bucket rule, and
wipe/refresh story in the `repro.perf` package docstring), and answers
from the in-process memo → disk cache → fresh race, in that order.
`engine.backend.default_backend_name()` (the platform rule) survives
only as the fallback when calibration is disabled
(``REPRO_AUTO_CALIBRATE=0``) or, off the TPU, the perf layer itself
fails.  On a TPU a failing perf layer or backend raises.

The race also **gates on parity**: each candidate's sweep output is
checked against the jnp oracle on the race data, and a backend whose
objective or centers deviate beyond ``parity_rtol`` is disqualified no
matter how fast it ran — that is how the bf16 sweep earns its place
(and how a numerically-broken kernel build loses it).
"""
from __future__ import annotations

import json
import os
import tempfile
from typing import Dict, Optional, Tuple

import numpy as np

from repro import obs

FORMAT_VERSION = 1
CALIB_NAME = "calibration.json"
ENV_DIR = "REPRO_CALIB_DIR"
ENV_DISABLE = "REPRO_AUTO_CALIBRATE"

# representative bucket when the caller has no shape in hand (the
# t11 engine-bench batch shape's bucket)
DEFAULT_SHAPE = (4096, 8, 16)
_RACE_N_CAP = 4096            # rows a race actually runs, however big
_N_LO, _N_HI = 256, 1 << 20   # the bucket clamp on n

_MEMO: Dict[str, str] = {}        # bucket_key -> winner (this process)

__all__ = ["shape_bucket", "bucket_key", "race_shape", "race_backends",
           "calibrated_backend_name", "calibration_dir",
           "calibration_path", "load_calibration", "store_calibration",
           "cached_peaks", "clear_memory_cache", "wipe"]


# ------------------------------------------------------------- buckets ---

def _pow2_ceil(v: int) -> int:
    return 1 << max(int(v) - 1, 0).bit_length() if v > 1 else 1


def shape_bucket(n: int, c: int, d: int) -> Tuple[int, int, int]:
    """The shape-bucket rule: every dim rounds UP to the next power of
    two, n clamped to [256, 2**20] — one measured winner serves every
    shape in its bucket."""
    return (min(max(_pow2_ceil(n), _N_LO), _N_HI),
            _pow2_ceil(c), _pow2_ceil(d))


def bucket_key(bucket: Tuple[int, int, int]) -> str:
    return "n{}_c{}_d{}".format(*bucket)


def race_shape(bucket: Tuple[int, int, int]) -> Tuple[int, int, int]:
    """The shape a race actually runs: the bucket representative with n
    capped at 4096 rows, so a cold first ``"auto"`` stays cheap even on
    interpret-mode backends (sweep time is linear in n; the backend
    ordering at 4096 rows is the ordering at 4M rows)."""
    n, c, d = bucket
    return (min(n, _RACE_N_CAP), c, d)


# ------------------------------------------------------------ the file ---

def calibration_dir() -> str:
    return os.environ.get(ENV_DIR) or os.path.join(
        os.getcwd(), ".cache", "perf")


def calibration_path(path: Optional[str] = None) -> str:
    return path if path is not None else os.path.join(
        calibration_dir(), CALIB_NAME)


def _registry_key() -> dict:
    """The content key: a stored file is valid iff this dict matches."""
    import jax

    from repro.engine import backend as eb
    eb._probe_kernel_backends()
    return {"format_version": FORMAT_VERSION,
            "platform": jax.default_backend(),
            "jax": jax.__version__,
            "backends": sorted(eb._REGISTRY)}


def load_calibration(path: Optional[str] = None) -> dict:
    """The calibration dict, or a fresh empty one if the file is
    missing, corrupt, or keyed for a different (platform, jax,
    backend-set) — corruption means re-race, never a crash."""
    fresh = {"key": _registry_key(), "winners": {}, "tiles": {},
             "peaks": None}
    try:
        with open(calibration_path(path)) as f:
            data = json.load(f)
    except (OSError, ValueError):
        return fresh
    if not isinstance(data, dict) or data.get("key") != fresh["key"]:
        return fresh
    for k, v in fresh.items():
        data.setdefault(k, v)
    return data


def store_calibration(data: dict, path: Optional[str] = None) -> str:
    """Atomic write (tmp + rename — the ChunkStore manifest rule: a
    torn write leaves the old file or none, never garbage)."""
    target = calibration_path(path)
    os.makedirs(os.path.dirname(target), exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=os.path.dirname(target),
                               suffix=".tmp")
    with os.fdopen(fd, "w") as f:
        json.dump(data, f, indent=1)
    os.replace(tmp, target)
    return target


def clear_memory_cache() -> None:
    """Drop the in-process memo (disk cache untouched) — a fresh
    `calibrated_backend_name` then re-reads the file."""
    _MEMO.clear()
    from . import autotune
    autotune._MEMO.clear()


def wipe(path: Optional[str] = None) -> None:
    """Delete the calibration file and the in-process memo — the next
    ``"auto"`` re-probes and re-races from scratch."""
    clear_memory_cache()
    try:
        os.remove(calibration_path(path))
    except OSError:
        pass


# ------------------------------------------------------------- the race --

def race_backends(shape: Tuple[int, int, int], *, m: float = 2.0,
                  warmup: int = 1, iters: int = 2,
                  parity_rtol: float = 2e-2,
                  dethrone_margin: float = 0.05) -> Tuple[str, dict]:
    """Time every registered backend's jitted sweep at ``shape``;
    return (winner_name, per-backend results).

    A backend is eligible only if its (centers, objective) agree with
    the jnp oracle within ``parity_rtol`` on the race data; parity
    failures are recorded, not raised.  So are errors off the TPU, where
    the kernels run in interpret mode for parity only; on a TPU a
    backend that fails to compile or run raises its error, since the
    race would otherwise hand the fit to the jnp reference in silence.
    ``jnp`` is always registered and always parity-true, so a winner
    always exists.

    Near-ties go to the oracle: a challenger must beat jnp's time by
    more than ``dethrone_margin`` (5%) to win — race jitter on a loaded
    host must not flip "auto" onto a reduced-precision or kernel path
    for a speedup inside the noise floor.
    """
    import jax

    from repro.engine import backend as eb
    from .microbench import time_fn
    from .roofline import _race_data

    eb._probe_kernel_backends()
    n, c, d = shape
    x, w, v = _race_data(n, c, d)
    ref_v, _, ref_q = (np.asarray(a) for a in
                       eb.get_backend("jnp").sweep(x, w, v, m))
    ref_scale = float(np.max(np.abs(ref_v))) or 1.0

    results: dict = {}
    for name in sorted(eb._REGISTRY):
        be = eb._REGISTRY[name]
        fn = jax.jit(lambda a, b, v0, _be=be: _be.sweep(a, b, v0, m))
        try:
            got_v, _, got_q = (np.asarray(a) for a in
                               jax.block_until_ready(fn(x, w, v)))
            dv = float(np.max(np.abs(got_v - ref_v))) / ref_scale
            dq = abs(float(got_q) - float(ref_q)) / (abs(float(ref_q))
                                                     or 1.0)
            ok = bool(np.isfinite(got_v).all()
                      and dv <= parity_rtol and dq <= parity_rtol)
            t = time_fn(fn, x, w, v, warmup=max(warmup - 1, 0),
                        iters=iters)
            results[name] = {"us": t * 1e6, "parity_ok": ok,
                             "center_rel_err": dv, "objective_rel_err": dq}
        except Exception as e:
            if eb.on_tpu():
                raise
            results[name] = {"error": repr(e), "parity_ok": False}
    eligible = {k: r for k, r in results.items() if r.get("parity_ok")}
    winner = min(eligible, key=lambda k: eligible[k]["us"])
    if winner != "jnp" and "jnp" in eligible and \
            eligible[winner]["us"] > (1.0 - dethrone_margin) * \
            eligible["jnp"]["us"]:
        winner = "jnp"
    return winner, results


def calibrated_backend_name(shape: Optional[Tuple[int, int, int]] = None,
                            *, path: Optional[str] = None,
                            refresh: bool = False,
                            m: float = 2.0) -> Optional[str]:
    """The measured winner for ``shape``'s bucket — memo → disk → race.

    Returns None when measured selection is disabled
    (``REPRO_AUTO_CALIBRATE=0``); `resolve_backend` then falls back to
    the platform-name rule.  ``refresh=True`` forces a re-race of this
    one bucket (the file's other entries survive).
    """
    if os.environ.get(ENV_DISABLE, "1") in ("0", "false", "no"):
        return None
    bucket = shape_bucket(*(shape if shape is not None else DEFAULT_SHAPE))
    key = bucket_key(bucket)
    if not refresh:
        if key in _MEMO:
            return _MEMO[key]
        data = load_calibration(path)
        hit = data["winners"].get(key)
        if hit:
            _MEMO[key] = hit["winner"]
            return hit["winner"]
    winner, results = race_backends(race_shape(bucket), m=m)
    obs.event("perf.calibrate.race", bucket=key, winner=winner,
              times_us={k: round(r["us"], 1) for k, r in results.items()
                        if "us" in r},
              parity={k: bool(r.get("parity_ok"))
                      for k, r in results.items()})
    data = load_calibration(path)   # re-read: keep concurrent winners
    data["winners"][key] = {
        "winner": winner,
        "raced_shape": list(race_shape(bucket)),
        "times_us": {k: round(r["us"], 1) for k, r in results.items()
                     if "us" in r},
        "parity": {k: bool(r.get("parity_ok")) for k, r in
                   results.items()},
        "errors": {k: r["error"] for k, r in results.items()
                   if "error" in r},
    }
    store_calibration(data, path)
    _MEMO[key] = winner
    return winner


# -------------------------------------------------------- probed peaks ---

def cached_peaks(*, path: Optional[str] = None, refresh: bool = False,
                 **probe_kw) -> dict:
    """The machine's probed peaks, cached in the calibration file under
    ``"peaks"`` (same content-key invalidation as the winners)."""
    data = load_calibration(path)
    if data["peaks"] and not refresh:
        return data["peaks"]
    from .microbench import probe_peaks
    peaks = probe_peaks(**probe_kw)
    data = load_calibration(path)
    data["peaks"] = peaks
    store_calibration(data, path)
    return peaks
