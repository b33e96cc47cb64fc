"""Sweep backends — the one place the Kolen–Hutcheson sweep is chosen.

The paper's entire speed claim reduces to one primitive, the O(n·c)
accumulation sweep (Alg. 1 body): recompute the membership term u_ik^m on
the fly and accumulate ``V_i += w_k·u_ik^m·x_k``, ``W_i += w_k·u_ik^m``.
Every layer (driver race, combiner, reducer, WFCMPB blocks, streaming
window) runs this same primitive; a *backend* is an implementation of it,
selected once by name instead of hand-threaded callables:

  ``jnp``               — pure-jnp reference (XLA fuses it well on CPU).
  ``jnp_bf16``          — mixed precision: the two (N,C,d) matmuls take
                          bf16 inputs, every accumulator (cross term,
                          v_num, w_i, q) stays f32 — candidate-raced,
                          never assumed faster (on TPU bf16 matmul peak
                          is ~2× f32; on CPU the emulation often loses).
  ``pallas``            — fused Pallas TPU kernel (interpret mode on CPU,
                          kept registered there for parity testing).
  ``pallas_accumulate`` — the raw-accumulator Pallas entry point
                          (`fcm_accumulate_pallas`): emits un-normalized
                          (v_num, w_i, q) sums, so chunks/slots/shards
                          add elementwise and normalize ONCE — the
                          streaming/merge-fusion backend.

``resolve_backend(None | "auto")`` selects by MEASUREMENT (PR 6): the
first "auto" per (platform, shape-bucket) runs a one-shot timed race of
every registered backend through `repro.perf.calibrate`, gated on
parity against the jnp oracle, and caches the winner on disk — later
resolutions (this process or the next) are a cache hit.  Callers that
know their workload pass ``shape=(n_records, n_clusters, dim)`` so the
race runs in the right bucket; without it a representative default
bucket is used.  The old platform-name rule (TPU → ``pallas``, else →
``jnp``) survives as `default_backend_name()`, the fallback when
calibration is disabled (``REPRO_AUTO_CALIBRATE=0``) or, off the TPU,
when the perf layer fails.  On a TPU no fallback hides a failed kernel:
a broken kernels import, perf layer or kernel compile raises.  The
Pallas backends register themselves from `repro.kernels.ops` on first
lookup, so this module has no hard kernel dependency.

The sweep math itself (pairwise distances, log-space membership terms)
lives here — it is the engine's foundation; `repro.core.fcm` re-exports
it for the paper-facing API.
"""
from __future__ import annotations

import importlib
from typing import Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro import obs

_D2_FLOOR = 1e-12  # distance floor: a record sitting exactly on a center
_F32 = jax.lax.Precision.HIGHEST  # f32 matmuls stay f32 on a TPU too


# ------------------------------------------------------------ sweep math ---

def pairwise_sqdist(x: jax.Array, centers: jax.Array) -> jax.Array:
    """‖x−v‖² via the MXU-friendly expansion x² + v² − 2·x·vᵀ.

    The cross term runs at full f32 precision on every platform (a TPU's
    default would round its inputs to bf16): the expansion cancels, and
    near-equidistant records would change their nearest center."""
    x = x.astype(jnp.float32)
    centers = centers.astype(jnp.float32)
    x2 = jnp.sum(x * x, axis=-1, keepdims=True)          # (N, 1)
    v2 = jnp.sum(centers * centers, axis=-1)             # (C,)
    cross = jnp.matmul(x, centers.T, precision=_F32)     # (N, C) — matmul
    return jnp.maximum(x2 + v2 - 2.0 * cross, _D2_FLOOR)


def _u_from_d2(d2: jax.Array, m: float) -> jax.Array:
    """Numerically-stable membership degrees u: the Eq.-5 ratio computed
    in log space with max-normalization (u_i = r_i/Σr_j,
    r_i = (d_min/d_i)^(1/(m−1)) ≤ 1), avoiding the d^(2/(m−1))
    overflow/underflow for m near 1."""
    expo = 1.0 / (m - 1.0)
    logd = jnp.log(d2)
    lmin = jnp.min(logd, axis=-1, keepdims=True)
    r = jnp.exp(-expo * (logd - lmin))              # (N, C), in (0, 1]
    return r / jnp.sum(r, axis=-1, keepdims=True)


def _um_from_d2(d2: jax.Array, m: float) -> jax.Array:
    """u^m — the membership *term* the sweep accumulates."""
    return jnp.power(_u_from_d2(d2, m), m)          # u^m, (N, C)


def membership_terms(x: jax.Array, centers: jax.Array, m: float) -> jax.Array:
    """u_ik^m for every record/center pair.  x: (N,d), centers: (C,d) → (N,C).

    Paper Eq. (5): numerator_i = ‖x−v_i‖^(2/(m−1)),
    denominator = Σ_i 1/numerator_i,  u_i^m = (numerator_i · denominator)^(−m).
    The denominator is computed once per record — this is the O(n·c) trick
    (naive FCM is O(n·c²) because the inner normalizing sum is re-evaluated
    per (i,k) pair).
    """
    return _um_from_d2(pairwise_sqdist(x, centers), m)


def fcm_accumulate(x, weights, centers, m):
    """Raw Alg.-1 accumulators (v_num, w_i, q) — normalization deferred.

    All three outputs are plain sums over records, so partial results
    from chunks/slots/shards add elementwise (and `jax.lax.psum`) before
    a single normalization — the property every merge topology exploits.
    """
    d2 = pairwise_sqdist(x, centers)
    wum = _um_from_d2(d2, m) * weights[:, None]     # w_k · u_ik^m
    w_i = jnp.sum(wum, axis=0)                      # (C,)
    v_num = jnp.matmul(wum.T, x.astype(jnp.float32),
                       precision=_F32)                # (C, d) — matmul
    q = jnp.sum(wum * d2)                           # objective, Eq. (2)
    return v_num, w_i, q


def normalize_accumulators(v_num, w_i, q):
    """The one deferred normalization: (v_num, w_i, q) → (v_new, w_i, q).

    Shape-polymorphic over leading axes: works for a single (C, d)/(C,)
    accumulator pair and for tenant-stacked (T, C, d)/(T, C) ones."""
    return v_num / jnp.maximum(w_i, _D2_FLOOR)[..., None], w_i, q


def fcm_sweep(x, weights, centers, m):
    """One full accumulation sweep (Alg. 1 body).  Returns (V_new, W, Q)."""
    return normalize_accumulators(*fcm_accumulate(x, weights, centers, m))


def fcm_accumulate_mixed(x, weights, centers, m,
                         compute_dtype=jnp.bfloat16):
    """Mixed-precision Alg.-1 accumulators: bf16 compute, f32 accumulate.

    The two O(N·C·d) contractions — the distance cross term and the
    center numerators — take ``compute_dtype`` inputs with f32
    accumulation (``preferred_element_type``); the O(N·C) membership
    math (log-space, transcendental-bound, cheap) and the three
    accumulators (v_num, w_i, q) stay f32, so partials still add
    exactly like the f32 backend's.  Distance assembly keeps f32
    squared norms: d² = x² + v² − 2·x·vᵀ is a cancellation, and bf16
    norms would poison small distances — the dominant cross term
    carries the precision loss instead, which objective-parity tests
    (and the calibration race's parity gate) bound at the fit level.
    """
    xc = x.astype(compute_dtype)
    vc = centers.astype(compute_dtype)
    xf = x.astype(jnp.float32)
    vf = centers.astype(jnp.float32)
    x2 = jnp.sum(xf * xf, axis=-1, keepdims=True)          # (N, 1) f32
    v2 = jnp.sum(vf * vf, axis=-1)                         # (C,)  f32
    cross = jax.lax.dot_general(                           # bf16 MXU,
        xc, vc, (((1,), (1,)), ((), ())),                  # f32 accum
        preferred_element_type=jnp.float32)                # (N, C)
    d2 = jnp.maximum(x2 + v2 - 2.0 * cross, _D2_FLOOR)
    wum = _um_from_d2(d2, m) * weights[:, None]            # f32 (N, C)
    w_i = jnp.sum(wum, axis=0)                             # (C,)  f32
    v_num = jax.lax.dot_general(                           # bf16 MXU,
        wum.astype(compute_dtype), xc,                     # f32 accum
        (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                # (C, d)
    q = jnp.sum(wum * d2)                                  # ()    f32
    return v_num, w_i, q


def _batched_in_axes(m) -> Union[int, None]:
    """vmap axis for ``m``: a scalar broadcasts to every tenant, a (T,)
    array gives each tenant its own fuzzifier (the per-tenant config
    axis)."""
    return 0 if jnp.ndim(m) else None


def fcm_accumulate_batched(x, weights, centers, m):
    """Alg.-1 accumulators vmapped over a leading tenant axis.

    ``x`` (T, N, d), ``weights`` (T, N), ``centers`` (T, C, d), ``m``
    scalar or (T,) → per-tenant (v_num (T, C, d), w_i (T, C), q (T,)).
    The N axis is a shared shape bucket: per-tenant row counts n_t ≤ N
    ride in as zero-weight phantom padding (`data.plane.pad_rows`), so
    padding is a no-op in every accumulator — T small models cost ONE
    launch instead of T."""
    return jax.vmap(fcm_accumulate,
                    in_axes=(0, 0, 0, _batched_in_axes(m)))(
        x, weights, centers, m)


def soft_assign(x: jax.Array, centers: jax.Array, m: float = 2.0) -> jax.Array:
    """Membership degrees u_ik (not raised to m) — for evaluation/serving.

    The naive ``d2**(1/(m−1))`` ratio overflows to inf (and its
    reciprocal underflows to 0) for m near 1, poisoning every row that
    contains a moderately distant center; this shares `_u_from_d2`, the
    log-space form the sweep itself accumulates (to the power m).
    """
    return _u_from_d2(pairwise_sqdist(x, centers), m)


def hard_assign(x: jax.Array, centers: jax.Array) -> jax.Array:
    return jnp.argmin(pairwise_sqdist(x, centers), axis=-1)


# -------------------------------------------------------------- backends ---

class SweepBackend:
    """One implementation of the accumulation sweep.

    Subclasses provide ``accumulate`` (raw sums) and may override
    ``sweep`` with a fused version.  The assignment helpers default to
    the shared jnp math, which writes the whole (N, C) distance matrix
    to memory and reads it back: cheap at small C, the larger cost at C
    in the thousands.  A backend may own the serve path there: the
    ``pallas`` backend answers ``hard_assign`` with a C-tiled kernel by
    a rule on the center count (`repro.kernels.ops.nearest_by_kernel`),
    and ``assigns_by_kernel`` says which path a center count takes.
    """

    name: str = "?"

    def accumulate(self, x, w, centers, m):
        """Raw (v_num, w_i, q) accumulators for one record chunk."""
        raise NotImplementedError

    def sweep(self, x, w, centers, m):
        """(v_new, w_i, q): accumulate + the one deferred normalization."""
        return normalize_accumulators(*self.accumulate(x, w, centers, m))

    def batched_accumulate(self, x, w, centers, m):
        """Raw accumulators for a TENANT-STACKED batch — the multi-model
        entry (PR 10): ``x`` (T, N, d), ``w`` (T, N), ``centers``
        (T, C, d), ``m`` scalar or (T,) → per-tenant (v_num, w_i, q)
        with leading T.  Default: `jax.vmap` of ``accumulate`` — one
        fused launch for all T models; backends whose kernels can't be
        vmapped override this."""
        return jax.vmap(self.accumulate,
                        in_axes=(0, 0, 0, _batched_in_axes(m)))(
            x, w, centers, m)

    def batched_sweep(self, x, w, centers, m):
        """Tenant-stacked sweep: batched accumulate + the per-tenant
        deferred normalization (shape-polymorphic
        `normalize_accumulators`)."""
        return normalize_accumulators(*self.batched_accumulate(
            x, w, centers, m))

    def soft_assign(self, x, centers, m=2.0):
        return soft_assign(x, centers, m)

    def hard_assign(self, x, centers):
        return hard_assign(x, centers)

    def assigns_by_kernel(self, n_centers: int) -> bool:
        """Whether ``hard_assign`` at ``n_centers`` runs a kernel of
        this backend rather than the shared jnp math."""
        return False

    def __repr__(self):
        return f"<SweepBackend {self.name}>"


class JnpBackend(SweepBackend):
    """Pure-jnp reference backend — the CPU default and the oracle."""

    name = "jnp"

    def accumulate(self, x, w, centers, m):
        return fcm_accumulate(x, w, centers, m)

    def sweep(self, x, w, centers, m):
        return fcm_sweep(x, w, centers, m)


class Bf16Backend(SweepBackend):
    """Mixed-precision sweep: bf16 matmul inputs, f32 accumulators
    (`fcm_accumulate_mixed`).  Enters the calibration race like every
    other backend and wins only where the hardware's bf16 path is
    actually faster AND the race's parity gate passes — it is never the
    platform default."""

    name = "jnp_bf16"

    def accumulate(self, x, w, centers, m):
        return fcm_accumulate_mixed(x, w, centers, m)


_REGISTRY: Dict[str, SweepBackend] = {}
_KERNELS_PROBED = False


def on_tpu() -> bool:
    """Whether this process runs on a TPU, where every fallback that
    would hide a failed kernel raises instead."""
    return jax.default_backend() == "tpu"


BackendLike = Union[None, str, SweepBackend]


def register_backend(backend: SweepBackend) -> SweepBackend:
    """Register (or replace) a backend under ``backend.name``."""
    _REGISTRY[backend.name] = backend
    return backend


def _probe_kernel_backends() -> None:
    """Import `repro.kernels.ops` once so its backends self-register.

    On a TPU a broken kernels layer raises the original import error:
    the Pallas sweep is the product there, and a silent fall back to the
    jnp reference would hide it.  Elsewhere the kernels only serve
    parity testing, so a failed import degrades to the jnp paths — but
    LOUDLY: exactly one warning per process, routed through the obs
    event sink (`obs.warn_once`) with the original import error kept in
    the event payload."""
    global _KERNELS_PROBED
    if _KERNELS_PROBED:
        return
    try:
        importlib.import_module("repro.kernels.ops")  # registers pallas
    except Exception as e:
        if on_tpu():
            raise
        obs.warn_once(
            "kernels_probe_failed",
            "repro.kernels.ops failed to import — Pallas sweep backends "
            f"are unavailable this process; falling back to jnp: {e!r}",
            stacklevel=3, error=repr(e))
    _KERNELS_PROBED = True


def available_backends() -> list:
    _probe_kernel_backends()
    return sorted(_REGISTRY)


def get_backend(name: str) -> SweepBackend:
    _probe_kernel_backends()
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown sweep backend {name!r}; registered: "
            f"{sorted(_REGISTRY)}") from None


def default_backend_name() -> str:
    """The platform-name rule: TPU → ``pallas``, anything else →
    ``jnp``.  Since PR 6 this is a FALLBACK, not the auto-selection:
    ``resolve_backend("auto")`` picks by measured race
    (`repro.perf.calibrate`) and only lands here when calibration is
    disabled.  The Pallas kernel's revisited-output-block accumulation
    is a Mosaic (TPU) semantic, so GPU hosts get the jnp reference too;
    on CPU the pallas backends stay registered in interpret mode for
    parity testing.  On a TPU the kernels import raises if it fails
    (`_probe_kernel_backends`), so ``pallas`` is always registered
    there."""
    if on_tpu():
        _probe_kernel_backends()
        return "pallas"
    return "jnp"


def _calibrated_name(shape: Optional[Tuple[int, int, int]]) -> Optional[str]:
    """Measured winner via `repro.perf.calibrate`, or None to fall back
    to the platform rule (calibration disabled).  A broken perf layer
    raises on a TPU; elsewhere it warns once and falls back, the same
    contract as the kernels probe."""
    try:
        from repro.perf.calibrate import calibrated_backend_name
        name = calibrated_backend_name(shape)
    except Exception as e:
        if on_tpu():
            raise
        obs.warn_once(
            "perf_calibration_failed",
            "repro.perf calibration failed — backend auto-selection "
            f"falling back to the platform-name rule: {e!r}",
            stacklevel=3, error=repr(e))
        return None
    return name if name in _REGISTRY else None


def resolve_backend(spec: BackendLike = None, *,
                    shape: Optional[Tuple[int, int, int]] = None
                    ) -> SweepBackend:
    """None/"auto" → measured winner for ``shape``'s bucket (platform
    rule as fallback); str → registry; object → itself.  ``shape`` is
    ``(n_records, n_clusters, dim)`` — pass it when known so the
    calibration race runs in the caller's own shape bucket."""
    if isinstance(spec, SweepBackend):
        return spec
    if spec is None or spec == "auto":
        _probe_kernel_backends()
        name = _calibrated_name(shape)
        return get_backend(name or default_backend_name())
    return get_backend(spec)


register_backend(JnpBackend())
register_backend(Bf16Backend())
