"""`repro.engine` — sweep backends + merge plans (PR 3 tentpole).

Covers the backend registry (names, auto-selection, extensibility),
backend parity on off-lane shapes THROUGH the engine API, merge-plan
topology equivalence, and the acceptance criterion that batch BigFCM,
WFCMPB, and the streaming window all converge to the same centers on
every backend.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import BigFCMConfig, bigfcm_fit, fcm, wfcmpb
from repro.core.metrics import fuzzy_objective
from repro.data import make_blobs
from repro.engine import (MergePlan, Summary, SweepBackend,
                          available_backends, default_backend_name,
                          fcm_accumulate, get_backend, merge_summaries,
                          register_backend, resolve_backend)
from repro.stream import StreamConfig, StreamingBigFCM

BACKENDS = ["jnp", "pallas", "pallas_accumulate"]

# C and d above the 128 MXU lane but NOT multiples of it — padding and
# phantom-center masking both in play on the kernel backends.
OFF_LANE_SHAPES = [(200, 129, 140), (96, 257, 129)]


def _rand(n, d, c, seed=0):
    rng = np.random.default_rng(seed + n + d + c)
    return (jnp.asarray(rng.normal(size=(n, d)).astype(np.float32)),
            jnp.asarray(rng.uniform(0.1, 3.0, size=(n,)).astype(np.float32)),
            jnp.asarray(rng.normal(size=(c, d)).astype(np.float32)))


# ------------------------------------------------------------- registry --

def test_registry_names_and_auto_rule():
    assert set(BACKENDS) | {"jnp_bf16"} <= set(available_backends())
    # the platform-name rule survives as the FALLBACK only
    want = "pallas" if jax.default_backend() == "tpu" else "jnp"
    assert default_backend_name() == want
    # "auto" picks by measured race (PR 6): on a CPU host the winner is
    # one of the full-speed jnp-family sweeps, never interpret-mode
    # pallas; which of the two wins is the machine's call, not ours
    for spec in (None, "auto"):
        got = resolve_backend(spec).name
        if jax.default_backend() == "cpu":
            assert got in ("jnp", "jnp_bf16")
        else:
            assert got in available_backends()
    be = get_backend("pallas")
    assert resolve_backend(be) is be
    with pytest.raises(KeyError, match="unknown sweep backend"):
        get_backend("cuda")


@pytest.fixture
def poisoned_kernels():
    """`repro.kernels.ops` whose import raises, with the registry and
    module cache restored afterwards."""
    import importlib.util
    import sys

    from repro.engine import backend as backend_mod

    saved_probed = backend_mod._KERNELS_PROBED
    saved_mods = {k: sys.modules.pop(k) for k in list(sys.modules)
                  if k.startswith("repro.kernels")}
    saved_backends = {k: backend_mod._REGISTRY.pop(k) for k in
                      ("pallas", "pallas_accumulate")
                      if k in backend_mod._REGISTRY}

    class _PoisonLoader:
        def create_module(self, spec):
            return None

        def exec_module(self, module):
            raise RuntimeError("poisoned kernels import (test)")

    class _Poison:
        def find_spec(self, name, path=None, target=None):
            if name == "repro.kernels.ops":
                return importlib.util.spec_from_loader(name,
                                                       _PoisonLoader())
            return None

    finder = _Poison()
    sys.meta_path.insert(0, finder)
    backend_mod._KERNELS_PROBED = False
    try:
        yield backend_mod
    finally:
        sys.meta_path.remove(finder)
        sys.modules.update(saved_mods)
        backend_mod._REGISTRY.update(saved_backends)
        backend_mod._KERNELS_PROBED = saved_probed


def test_broken_kernels_import_warns_and_degrades_to_jnp(poisoned_kernels):
    """PR-6 satellite: a poisoned `repro.kernels.ops` import must emit
    one RuntimeWarning carrying the original error — never a silent
    degrade to the 50×-slower reference path — and the jnp backends must
    keep resolving."""
    backend_mod = poisoned_kernels
    with pytest.warns(RuntimeWarning, match="poisoned kernels import"):
        backend_mod._probe_kernel_backends()
    # degraded but alive: the jnp family still resolves
    assert get_backend("jnp").name == "jnp"
    assert "pallas" not in backend_mod._REGISTRY
    with pytest.raises(KeyError):
        get_backend("pallas")


def test_broken_kernels_import_raises_on_tpu(poisoned_kernels, monkeypatch):
    """On a TPU the Pallas sweep is the product: a poisoned kernels
    import raises the original error from every entry that probes, and
    nothing warns and degrades to jnp."""
    import warnings

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        for probe in (poisoned_kernels._probe_kernel_backends,
                      default_backend_name,
                      lambda: get_backend("jnp")):
            with pytest.raises(RuntimeError,
                               match="poisoned kernels import"):
                probe()
    assert "pallas" not in poisoned_kernels._REGISTRY


@pytest.mark.parametrize("name", ["pairwise_sqdist", "fcm_accumulate",
                                  "fcm_accumulate_pallas"])
def test_f32_contractions_ask_for_full_precision(name):
    """A TPU rounds the inputs of a default-precision f32 matmul to
    bf16.  Every contraction of the f32 sweeps (jnp and kernel) asks
    for HIGHEST, so they agree with an f32 reference on the chip too."""
    from functools import partial

    from repro.engine import backend
    from repro.kernels.fcm_update import fcm_accumulate_pallas

    x, w, v = _rand(16, 5, 3)
    fn, args = {
        "pairwise_sqdist": (backend.pairwise_sqdist, (x, v)),
        "fcm_accumulate": (partial(backend.fcm_accumulate, m=2.0),
                           (x, w, v)),
        "fcm_accumulate_pallas": (partial(fcm_accumulate_pallas, m=2.0,
                                          interpret=True), (x, w, v)),
    }[name]
    text = str(jax.make_jaxpr(fn)(*args))
    n_dots = text.count("dot_general[")
    assert n_dots >= 1
    assert text.count("precision=(Precision.HIGHEST, Precision.HIGHEST)") \
        == n_dots, text


# ----------------------------------------------------- parity (engine) --

@pytest.mark.parametrize("name", BACKENDS)
@pytest.mark.parametrize("n,d,c", OFF_LANE_SHAPES)
def test_backend_parity_off_lane_shapes(name, n, d, c):
    """jnp, pallas (interpret on CPU), and pallas_accumulate+normalize
    produce identical (v_new, w_i, q) and raw accumulators through the
    engine API."""
    x, w, v = _rand(n, d, c)
    be = get_backend(name)
    for got, want in [(be.sweep(x, w, v, 2.0),
                       get_backend("jnp").sweep(x, w, v, 2.0)),
                      (be.accumulate(x, w, v, 2.0),
                       fcm_accumulate(x, w, v, 2.0))]:
        for g, e in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(e),
                                       rtol=3e-4, atol=3e-3)


def test_custom_backend_registration_and_windowed_accumulate_path():
    """The registry is open: a wrapper backend slots into every consumer,
    and the ``windowed`` plan reaches it ONLY through the raw accumulate
    entry point (the fcm_accumulate_pallas fusion seam)."""
    calls = {"accumulate": 0, "sweep": 0}

    class Counting(SweepBackend):
        name = "counting"

        def accumulate(self, x, w, centers, m):
            calls["accumulate"] += 1
            return fcm_accumulate(x, w, centers, m)

        def sweep(self, x, w, centers, m):
            calls["sweep"] += 1
            return super().sweep(x, w, centers, m)

    from repro.engine import backend as backend_mod
    register_backend(Counting())
    try:
        rng = np.random.default_rng(1)
        s = Summary(
            jnp.asarray(rng.normal(size=(4, 3, 2)).astype(np.float32)),
            jnp.asarray(rng.uniform(0.5, 2, size=(4, 3))
                        .astype(np.float32)))
        merge_summaries(s, MergePlan("windowed", m=2.0), backend="counting")
        assert calls["accumulate"] == 4 * 2  # per slot × (loop trace+final)
        assert calls["sweep"] == 0
    finally:  # don't leak the test backend into the process registry
        backend_mod._REGISTRY.pop("counting", None)


# --------------------------------------------------------- merge plans --

def test_flat_and_windowed_topologies_agree_exactly():
    """``windowed`` is the flat reduce with the normalization deferred
    across per-slot raw sums — same math, same fixed point."""
    rng = np.random.default_rng(3)
    s = Summary(jnp.asarray(rng.normal(size=(6, 4, 3)).astype(np.float32)),
                jnp.asarray(rng.uniform(0.5, 2, size=(6, 4))
                            .astype(np.float32)))
    plan = dict(m=2.0, eps=1e-12, max_iter=300)
    # a math-identity assertion: pin the deterministic f32 reference
    # backend ("auto" may legitimately pick jnp_bf16, whose matmul
    # rounding differs between the two accumulation shapes)
    rf = merge_summaries(s, MergePlan("flat", **plan), backend="jnp")
    rw = merge_summaries(s, MergePlan("windowed", **plan), backend="jnp")
    np.testing.assert_allclose(np.asarray(rf.summary.centers),
                               np.asarray(rw.summary.centers), atol=1e-4)
    np.testing.assert_allclose(np.asarray(rf.summary.masses),
                               np.asarray(rw.summary.masses), rtol=1e-4)


def test_pairwise_topology_comparable_quality_not_mass():
    """The pairwise tree fits the same sketch comparably well — but mass
    is NOT conserved by WFCM (Σ u^m < 1 for m > 1), so its extra merge
    rounds legitimately shrink total mass vs the single flat round."""
    rng = np.random.default_rng(4)
    s = Summary(jnp.asarray(rng.normal(size=(4, 3, 2)).astype(np.float32)),
                jnp.asarray(rng.uniform(0.5, 2, size=(4, 3))
                            .astype(np.float32)))
    rt = merge_summaries(s, MergePlan("pairwise", m=2.0))
    rf = merge_summaries(s, MergePlan("flat", m=2.0))
    pts = s.centers.reshape(-1, 2)
    wts = s.masses.reshape(-1)
    q_t = float(fuzzy_objective(pts, rt.summary.centers, point_weights=wts))
    q_f = float(fuzzy_objective(pts, rf.summary.centers, point_weights=wts))
    assert np.isfinite(np.asarray(rt.summary.centers)).all()
    assert q_t <= 1.25 * q_f and q_f <= 1.25 * q_t
    assert float(rt.summary.masses.sum()) > 0


def test_merge_topology_agreement_centers_objective_only():
    """Regression (ISSUE-4 satellite): flat, pairwise, and windowed
    reduce a well-separated sketch stack to the SAME centers and
    objective.  Masses are intentionally NOT compared across topologies
    — WFCM does not conserve mass (Σ_i u^m < 1 for m > 1), so
    topologies running different merge rounds legitimately disagree on
    total mass; assert that caveat explicitly instead.
    """
    rng = np.random.default_rng(11)
    c, d, slots = 4, 3, 6
    true = rng.normal(0.0, 6.0, size=(c, d)).astype(np.float32)
    s = Summary(
        jnp.asarray(true[None] + 0.1 * rng.normal(
            size=(slots, c, d)).astype(np.float32)),
        jnp.asarray(rng.uniform(0.8, 1.2, size=(slots, c))
                    .astype(np.float32)))
    plan = dict(m=2.0, eps=1e-12, max_iter=300)
    # pinned to the f32 reference: the windowed-vs-flat mass identity
    # below is asserted at rtol 1e-4, tighter than bf16 rounding
    res = {t: merge_summaries(s, MergePlan(t, **plan), backend="jnp")
           for t in ("flat", "pairwise", "windowed")}

    # centers: all three topologies land on the same optimum
    ref = np.sort(np.asarray(res["flat"].summary.centers), axis=0)
    for t in ("pairwise", "windowed"):
        np.testing.assert_allclose(
            np.sort(np.asarray(res[t].summary.centers), axis=0), ref,
            atol=0.05, err_msg=f"topology {t} centers diverged")

    # objective: each topology fits the sketch points equally well
    pts, wts = s.centers.reshape(-1, d), s.masses.reshape(-1)
    qs = {t: float(fuzzy_objective(pts, r.summary.centers,
                                   point_weights=wts))
          for t, r in res.items()}
    for t in ("pairwise", "windowed"):
        assert qs[t] <= 1.05 * qs["flat"] and qs["flat"] <= 1.05 * qs[t]

    # the documented mass caveat, asserted explicitly on an OVERLAPPING
    # stack (near-one-hot memberships would hide it): every WFCM round
    # shrinks mass below its input (Σ_i u^m < 1 for m > 1), so
    # topologies that run different rounds land on measurably DIFFERENT
    # totals — which is exactly why masses are never compared across
    # topologies anywhere in this suite
    fuzzy = Summary(
        jnp.asarray(rng.normal(0.0, 2.0, size=(c, d)).astype(np.float32)
                    [None] + 0.8 * rng.normal(
                        size=(slots, c, d)).astype(np.float32)),
        jnp.asarray(rng.uniform(0.8, 1.2, size=(slots, c))
                    .astype(np.float32)))
    fres = {t: merge_summaries(fuzzy, MergePlan(t, **plan),
                               backend="jnp")
            for t in ("flat", "pairwise", "windowed")}
    m_in = float(fuzzy.masses.sum())
    m_flat = float(fres["flat"].summary.masses.sum())
    m_pair = float(fres["pairwise"].summary.masses.sum())
    assert m_flat < 0.99 * m_in
    assert m_pair < 0.99 * m_in
    assert abs(m_pair - m_flat) / m_flat > 1e-3   # topology-dependent
    # flat and windowed are the same math (deferred normalization), so
    # their masses DO agree — the caveat is about differing rounds
    np.testing.assert_allclose(
        np.asarray(fres["windowed"].summary.masses).sum(), m_flat,
        rtol=1e-4)


def test_merge_single_slot_and_bad_plan():
    s = Summary(jnp.ones((1, 2, 3)), jnp.ones((1, 2)))
    r = merge_summaries(s, MergePlan("flat"))
    np.testing.assert_array_equal(np.asarray(r.summary.centers),
                                  np.ones((2, 3)))
    assert int(r.n_iter) == 0
    # with an explicit seed the reducer WFCM still polishes a lone slot
    rng = np.random.default_rng(9)
    s1 = Summary(jnp.asarray(rng.normal(size=(1, 3, 2)).astype(np.float32)),
                 jnp.ones((1, 3)))
    rp = merge_summaries(s1, MergePlan("flat", eps=1e-12),
                         init=s1.centers[0] + 0.1)
    assert int(rp.n_iter) >= 1
    assert np.isfinite(np.asarray(rp.summary.centers)).all()
    with pytest.raises(ValueError, match="topology"):
        MergePlan("ring")
    with pytest.raises(ValueError, match="stacked"):
        merge_summaries(Summary(jnp.ones((2, 3)), jnp.ones((2,))))
    s2 = Summary(jnp.ones((2, 2, 3)), jnp.ones((2, 2)))
    with pytest.raises(ValueError, match="pairwise"):
        merge_summaries(s2, MergePlan("pairwise"), init=jnp.ones((2, 3)))


# ------------------------------------- convergence across layers/backends --

@pytest.mark.parametrize("name", BACKENDS)
def test_batch_wfcmpb_stream_converge_per_backend(name):
    """Acceptance: batch BigFCM, WFCMPB, and the streaming window reach
    the same centers on every backend (pallas in interpret mode on CPU)."""
    x, y = make_blobs(900, 4, 3, seed=7)
    x = jnp.asarray(x)
    ref = np.sort(np.asarray(
        fcm(x, x[:3], m=2.0, eps=1e-9, max_iter=200).centers), axis=0)

    batch = bigfcm_fit(x, BigFCMConfig(n_clusters=3, sample_size=256,
                                       max_iter=150, backend=name, seed=1))
    np.testing.assert_allclose(np.sort(np.asarray(batch.centers), axis=0),
                               ref, atol=0.3)

    pb = wfcmpb(x, x[:3], m=2.0, eps=1e-8, max_iter=150, block_size=512,
                backend=name)
    np.testing.assert_allclose(np.sort(np.asarray(pb.centers), axis=0),
                               ref, atol=0.3)

    cfg = StreamConfig(n_clusters=3, window=3, max_iter=150,
                       driver_sample=256, backend=name, seed=0)
    model = StreamingBigFCM(cfg)
    for i in range(3):
        model.ingest(x[i * 300:(i + 1) * 300])
    np.testing.assert_allclose(
        np.sort(np.asarray(model.state.centers), axis=0), ref, atol=0.35)


@pytest.mark.parametrize("plan", ["windowed", "pairwise", "flat"])
def test_stream_merge_plans_all_converge(plan):
    x, _ = make_blobs(900, 4, 3, seed=8)
    ref = np.sort(np.asarray(
        fcm(jnp.asarray(x), jnp.asarray(x[:3]), m=2.0, eps=1e-9,
            max_iter=200).centers), axis=0)
    cfg = StreamConfig(n_clusters=3, window=3, max_iter=150,
                       driver_sample=256, merge_plan=plan, seed=0)
    model = StreamingBigFCM(cfg)
    for i in range(3):
        model.ingest(x[i * 300:(i + 1) * 300])
    np.testing.assert_allclose(
        np.sort(np.asarray(model.state.centers), axis=0), ref, atol=0.35)
