"""`repro.perf` — measured performance plane (PR 6 tentpole).

Covers the acceptance criteria: ``resolve_backend("auto")`` selects its
backend BY MEASUREMENT on this host (race ran, winner cached), the
calibration cache is reused without re-racing, invalidates when the
registered-backend set changes, and survives a corrupt file; the
`jnp_bf16` mixed-precision sweep passes objective parity at the fit
level; the Pallas block autotuner persists per-bucket configs that the
kernel call sites pick up; and the roofline layer's analytic model /
achieved-vs-peak rows are self-consistent.

Every test runs against an isolated calibration dir (``REPRO_CALIB_DIR``
→ tmp_path) with the in-process memos cleared, so nothing leaks into
the repo's ``.cache/perf`` or across tests.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import BigFCMConfig, bigfcm_fit
from repro.core.metrics import fuzzy_objective
from repro.data import make_blobs
from repro.engine import (fcm_accumulate, fcm_accumulate_mixed,
                          get_backend, resolve_backend)
from repro.engine import backend as backend_mod
from repro.perf import autotune, calibrate
from repro.perf.calibrate import (bucket_key, calibrated_backend_name,
                                  load_calibration, race_shape,
                                  shape_bucket)
from repro.perf.microbench import probe_peaks, time_fn
from repro.perf.roofline import (kernel_roofline, roofline_report,
                                 sweep_bytes, sweep_flops,
                                 sweep_intensity)

ON_CPU = jax.default_backend() == "cpu"

# small bucket so races/tunes in this file stay ~seconds on 1 CPU core
SHAPE = (300, 3, 4)


@pytest.fixture
def calib_dir(tmp_path, monkeypatch):
    """Isolated calibration store: env-redirected dir + cleared memos."""
    monkeypatch.setenv(calibrate.ENV_DIR, str(tmp_path))
    calibrate.clear_memory_cache()
    yield tmp_path
    calibrate.clear_memory_cache()


def _stub_race(calls, winner="jnp"):
    """A race stand-in that records invocations and returns instantly."""
    def race(shape, *, m=2.0, **kw):
        calls.append(tuple(shape))
        return winner, {winner: {"us": 1.0, "parity_ok": True,
                                 "center_rel_err": 0.0,
                                 "objective_rel_err": 0.0}}
    return race


# ---------------------------------------------------------- bucket rule --

def test_shape_bucket_rule():
    # every dim rounds UP to the next power of two, n clamped to
    # [256, 2**20]; the race itself caps n at 4096
    assert shape_bucket(300, 3, 4) == (512, 4, 4)
    assert shape_bucket(10, 8, 16) == (256, 8, 16)
    assert shape_bucket(1 << 24, 129, 1) == (1 << 20, 256, 1)
    assert race_shape((1 << 20, 8, 16)) == (4096, 8, 16)
    assert race_shape((256, 8, 16)) == (256, 8, 16)


# ------------------------------------------------- measured auto-select --

def test_auto_selects_by_measurement(calib_dir):
    """Acceptance: "auto" runs a real race, caches the winner on disk,
    and on this CPU box lands on jnp or jnp_bf16 — never the 30-50×
    slower interpret-mode Pallas paths."""
    be = resolve_backend("auto", shape=SHAPE)
    if ON_CPU:
        assert be.name in ("jnp", "jnp_bf16")

    path = os.path.join(str(calib_dir), calibrate.CALIB_NAME)
    assert os.path.exists(path)          # the race ran and persisted
    with open(path) as f:
        data = json.load(f)
    key = bucket_key(shape_bucket(*SHAPE))
    entry = data["winners"][key]
    assert entry["winner"] == be.name
    # every registered backend entered the race and was timed or errored
    raced = set(entry["times_us"]) | set(entry["errors"])
    assert set(backend_mod._REGISTRY) <= raced
    # the winner won on time among parity-passing candidates (near-ties
    # within the 5% dethrone margin go to the jnp oracle)
    assert entry["parity"][be.name] is True
    eligible = {k: v for k, v in entry["times_us"].items()
                if entry["parity"].get(k)}
    fastest = min(eligible, key=eligible.get)
    assert entry["winner"] == fastest or (
        entry["winner"] == "jnp"
        and eligible[fastest] > 0.95 * eligible["jnp"])
    # jnp is the oracle: always parity-true
    assert entry["parity"]["jnp"] is True


def test_cache_reuse_no_rerace(calib_dir, monkeypatch):
    calls = []
    monkeypatch.setattr(calibrate, "race_backends", _stub_race(calls))
    assert calibrated_backend_name(SHAPE) == "jnp"
    assert len(calls) == 1
    # second resolve: in-process memo hit
    assert calibrated_backend_name(SHAPE) == "jnp"
    assert len(calls) == 1
    # new process simulation: memo cleared, disk hit — still no re-race
    calibrate.clear_memory_cache()
    assert calibrated_backend_name(SHAPE) == "jnp"
    assert len(calls) == 1
    # a different bucket races independently
    assert calibrated_backend_name((5000, 3, 4)) == "jnp"
    assert len(calls) == 2


def test_cache_invalidates_on_backend_set_change(calib_dir, monkeypatch):
    calls = []
    monkeypatch.setattr(calibrate, "race_backends", _stub_race(calls))
    calibrated_backend_name(SHAPE)
    assert len(calls) == 1

    class Dummy(backend_mod.JnpBackend):
        name = "dummy_test_backend"

    backend_mod.register_backend(Dummy())
    try:
        calibrate.clear_memory_cache()
        # registered-backend set changed → stored key mismatches → re-race
        calibrated_backend_name(SHAPE)
        assert len(calls) == 2
    finally:
        backend_mod._REGISTRY.pop("dummy_test_backend", None)
        calibrate.clear_memory_cache()


def test_corrupt_cache_falls_back_to_fresh_race(calib_dir, monkeypatch):
    calls = []
    monkeypatch.setattr(calibrate, "race_backends", _stub_race(calls))
    calibrated_backend_name(SHAPE)
    path = calibrate.calibration_path()
    with open(path, "w") as f:
        f.write("{ this is not json")
    calibrate.clear_memory_cache()
    # corrupt file → re-race, never a crash
    assert calibrated_backend_name(SHAPE) == "jnp"
    assert len(calls) == 2
    with open(path) as f:                # and the store healed itself
        assert json.load(f)["winners"]

    # a valid-JSON file with a foreign content key is equally discarded
    with open(path, "w") as f:
        json.dump({"key": {"format_version": -1}, "winners": {
            "n512_c4_d4": {"winner": "pallas"}}}, f)
    calibrate.clear_memory_cache()
    assert calibrated_backend_name(SHAPE) == "jnp"
    assert len(calls) == 3


def test_disable_env_skips_measurement(calib_dir, monkeypatch):
    def boom(*a, **k):
        raise AssertionError("race must not run when disabled")
    monkeypatch.setattr(calibrate, "race_backends", boom)
    monkeypatch.setenv(calibrate.ENV_DISABLE, "0")
    assert calibrated_backend_name(SHAPE) is None
    # resolve_backend falls back to the platform rule
    want = "pallas" if jax.default_backend() == "tpu" else "jnp"
    assert resolve_backend("auto", shape=SHAPE).name == want


def test_wipe_forces_rerace(calib_dir, monkeypatch):
    calls = []
    monkeypatch.setattr(calibrate, "race_backends", _stub_race(calls))
    calibrated_backend_name(SHAPE)
    calibrate.wipe()
    assert not os.path.exists(calibrate.calibration_path())
    calibrated_backend_name(SHAPE)
    assert len(calls) == 2


# ------------------------------------------------- fail loud on a TPU --

class _BrokenBackend(backend_mod.SweepBackend):
    """A backend whose sweep raises, like a kernel Mosaic refuses."""
    name = "a_broken"

    def accumulate(self, x, w, centers, m):
        raise RuntimeError("kernel failed to compile (test)")


def test_race_records_a_failing_backend_off_tpu(calib_dir, monkeypatch):
    monkeypatch.setitem(backend_mod._REGISTRY, "a_broken",
                        _BrokenBackend())
    winner, results = calibrate.race_backends((256, 4, 4))
    assert winner != "a_broken"
    assert "kernel failed to compile" in results["a_broken"]["error"]


def test_race_raises_a_failing_backend_on_tpu(calib_dir, monkeypatch):
    monkeypatch.setitem(backend_mod._REGISTRY, "a_broken",
                        _BrokenBackend())
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="kernel failed to compile"):
        calibrate.race_backends((256, 4, 4))


@pytest.mark.parametrize("platform", ["cpu", "tpu"])
def test_broken_perf_layer_raises_only_on_tpu(calib_dir, monkeypatch,
                                              platform):
    """The catches around the calibration race and the block autotuner
    fall back off the TPU and raise on it."""
    from repro.kernels import ops

    def boom(*a, **k):
        raise RuntimeError("perf layer broken (test)")

    monkeypatch.setattr(calibrate, "calibrated_backend_name", boom)
    monkeypatch.setattr(autotune, "tuned_blocks", boom)
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    x, v = jnp.zeros((64, 4)), jnp.zeros((3, 4))
    if platform == "tpu":
        with pytest.raises(RuntimeError, match="perf layer broken"):
            resolve_backend("auto", shape=SHAPE)
        with pytest.raises(RuntimeError, match="perf layer broken"):
            ops._blocks_for(x, v, None, None)
    else:
        with pytest.warns(RuntimeWarning, match="perf layer broken"):
            assert resolve_backend("auto", shape=SHAPE).name == "jnp"
        assert ops._blocks_for(x, v, None, None) == autotune.DEFAULT_BLOCKS


def test_tuned_blocks_are_keyed_by_platform(calib_dir, monkeypatch):
    """Blocks tuned on one platform never reach another, from the file
    or the in-process memo."""
    autotune.tune_sweep_blocks(SHAPE, tiles=(512,), lanes=(32,), iters=1)
    assert autotune.tuned_blocks(SHAPE)["lane"] == 32
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert autotune.tuned_blocks(SHAPE) is None


# ----------------------------------------------------- jnp_bf16 parity --

def test_bf16_accumulators_match_f32_sweep():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(400, 8)).astype(np.float32))
    w = jnp.asarray(rng.uniform(0.5, 2.0, size=(400,)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(5, 8)).astype(np.float32))
    got = fcm_accumulate_mixed(x, w, v, 2.0)
    want = fcm_accumulate(x, w, v, 2.0)
    for g, e in zip(got, want):
        assert g.dtype == jnp.float32     # f32 accumulators, always
        np.testing.assert_allclose(np.asarray(g), np.asarray(e),
                                   rtol=2e-2, atol=2e-2)


def test_bf16_objective_parity_at_fit_level():
    """The gate that earns jnp_bf16 its registry entry: a full BigFCM
    fit with the mixed-precision sweep reaches the same objective as the
    f32 fit (rel. diff ≪ the race's 2e-2 parity budget)."""
    x, _ = make_blobs(600, 4, 3, seed=5)
    x = jnp.asarray(x)
    qs = {}
    for name in ("jnp", "jnp_bf16"):
        res = bigfcm_fit(x, BigFCMConfig(n_clusters=3, sample_size=256,
                                         max_iter=120, backend=name,
                                         seed=1))
        assert np.isfinite(np.asarray(res.centers)).all()
        qs[name] = float(fuzzy_objective(x, res.centers))
    assert abs(qs["jnp_bf16"] - qs["jnp"]) / qs["jnp"] < 2e-2


# ------------------------------------------------------- block autotune --

def test_autotune_persists_and_kernels_pick_it_up(calib_dir):
    shape = (256, 4, 8)
    cfg = autotune.tune_sweep_blocks(shape, tiles=(128,), lanes=(32,),
                                     iters=1)
    assert (cfg["tile_n"], cfg["lane"]) == (128, 32)
    assert cfg["times_us"]              # the grid actually ran

    # persisted under "tiles" in the same calibration file
    key = bucket_key(shape_bucket(*shape))
    assert load_calibration()["tiles"][key]["lane"] == 32
    # survives a process restart (memo cleared → disk hit, no search)
    calibrate.clear_memory_cache()
    assert autotune.tuned_blocks(shape)["tile_n"] == 128
    # second tune call is a cached lookup, not a fresh search
    assert autotune.tune_sweep_blocks(shape) is not None

    # kernel call sites resolve the tuned config for this bucket
    from repro.kernels.ops import _blocks_for
    x, v = jnp.zeros((256, 8)), jnp.zeros((4, 8))
    assert _blocks_for(x, v, None, None) == {"tile_n": 128, "lane": 32}
    # explicit args always win over the tuned config
    assert _blocks_for(x, v, 512, 128) == {"tile_n": 512, "lane": 128}


def test_untuned_bucket_keeps_defaults(calib_dir):
    from repro.kernels.fcm_update import LANE
    from repro.kernels.ops import _blocks_for
    assert autotune.tuned_blocks((64, 2, 2)) is None   # never searches
    x, v = jnp.zeros((64, 2)), jnp.zeros((2, 2))
    assert _blocks_for(x, v, None, None) == {"tile_n": 1024, "lane": LANE}


def test_tuned_blocks_parity_vs_jnp(calib_dir):
    """The tuned (small-lane) kernel config is a speed knob, not a math
    change: interpret-mode accumulate at lane=32 matches the jnp oracle."""
    from repro.kernels.fcm_update import fcm_accumulate_pallas
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(256, 8)).astype(np.float32))
    w = jnp.asarray(rng.uniform(0.5, 2.0, size=(256,)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(4, 8)).astype(np.float32))
    got = fcm_accumulate_pallas(x, w, v, 2.0, tile_n=128, lane=32,
                                interpret=True)
    want = fcm_accumulate(x, w, v, 2.0)
    for g, e in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(e),
                                   rtol=3e-4, atol=3e-3)


# ------------------------------------------------------------- roofline --

def test_sweep_analytic_model():
    n, c, d = 1024, 8, 16
    assert sweep_flops(n, c, d) == pytest.approx(
        4.0 * n * c * d + 2.0 * n * d + 2.0 * c * d + 14.0 * n * c)
    # streaming minimum: X dominates; membership matrix NOT counted
    assert sweep_bytes(n, c, d) < 4.0 * (n * d + n + 2 * c * d + c + 1) + 5
    # intensity ≈ C for d ≫ 1 — the compute-bound-for-large-C rule
    assert sweep_intensity(10_000, 256, 256) == pytest.approx(256, rel=0.1)
    assert sweep_intensity(10_000, 4, 256) < 8


def test_kernel_roofline_row_fields():
    peaks = {"stream_bytes_per_s": 1e9, "matmul_f32_flops_per_s": 1e10,
             "matmul_bf16_flops_per_s": 5e9}
    row = kernel_roofline("jnp", (512, 4, 8), peaks=peaks, iters=1)
    assert row["backend"] == "jnp" and row["platform"] == \
        jax.default_backend()
    assert row["seconds"] > 0 and row["records_per_s"] > 0
    assert row["achieved_flops_per_s"] == pytest.approx(
        sweep_flops(512, 4, 8) / row["seconds"])
    assert row["frac_of_peak_flops"] == pytest.approx(
        row["achieved_flops_per_s"] / peaks["matmul_f32_flops_per_s"])
    assert row["bound"] in ("compute", "memory")
    assert 0 < row["frac_of_bound"]
    assert row["intensity_flop_per_byte"] == pytest.approx(
        sweep_intensity(512, 4, 8))

    # a bf16 backend is measured against the bf16 matmul peak
    row16 = kernel_roofline("jnp_bf16", (512, 4, 8), peaks=peaks, iters=1)
    assert row16["frac_of_peak_flops"] == pytest.approx(
        row16["achieved_flops_per_s"] / peaks["matmul_bf16_flops_per_s"])


def test_roofline_report_errors_are_rows_not_crashes():
    peaks = {"stream_bytes_per_s": 1e9, "matmul_f32_flops_per_s": 1e10,
             "matmul_bf16_flops_per_s": 5e9}
    rep = roofline_report([(256, 3, 4)], backends=["jnp", "no_such"],
                          peaks=peaks, iters=1)
    assert len(rep["rows"]) == 2
    by_name = {r["backend"]: r for r in rep["rows"]}
    assert "error" not in by_name["jnp"]
    assert "error" in by_name["no_such"]


def test_probe_peaks_smoke(calib_dir):
    peaks = probe_peaks(stream_floats=(1 << 14,), matmul_ns=(64,),
                        iters=1)
    for k in ("stream_bytes_per_s", "matmul_f32_flops_per_s",
              "matmul_bf16_flops_per_s"):
        assert np.isfinite(peaks[k]) and peaks[k] > 0
    assert peaks["probe"]["platform"] == jax.default_backend()
    # cached_peaks stores them in the calibration file, probes once
    calls = []
    import repro.perf.microbench as mb
    orig = mb.probe_peaks

    def counting(**kw):
        calls.append(kw)
        return orig(stream_floats=(1 << 14,), matmul_ns=(64,), iters=1)
    mb.probe_peaks = counting
    try:
        p1 = calibrate.cached_peaks()
        p2 = calibrate.cached_peaks()
        assert len(calls) == 1 and p1 == p2
    finally:
        mb.probe_peaks = orig


def test_time_fn_median():
    xs = jnp.arange(1024, dtype=jnp.float32)
    t = time_fn(jax.jit(lambda a: a * 2.0), xs, warmup=1, iters=3)
    assert np.isfinite(t) and t > 0
