"""The nearest-center cells' files on the CPU: the float64 check against
planted answers and against `reference.assignment_gaps`, the work
counts, the readers of the three per-layer metrics, the pool's law, and
the ``serve_nearest`` traffic's set-up, window and check at a small
size."""
import json
import os
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src"),
                os.path.join(ROOT, "bench")]

import jax  # noqa: E402

from bench.harness import nearest  # noqa: E402
from bench.harness import trace as tr  # noqa: E402
from bench.harness.cell import BENCH, Cell, load_cell, \
    metric_reader  # noqa: E402
from bench.harness.data import make_mixture, seed_key, \
    DATA_KEY  # noqa: E402
from bench.harness.reference import SAME_CENTER, \
    assignment_gaps  # noqa: E402

import run  # noqa: E402  (bench/run.py)

SEED = 2**33 + 15          # larger than 32 bits, as run seeds may be
CELL = "sift-ivf65536.ingest-over"
READERS = ("nearest_ms.serve", "nearest_roofline.serve",
           "tiled_share.serve")


def _points(seed=0, n=300, c=64, d=16, alias=SAME_CENTER / 10):
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(c, d))
    centers[7] = centers[3]                        # an exact duplicate
    centers[9] = centers[5] + alias                # an alias
    x = (centers[rng.integers(0, c, n)] + 0.4 * rng.normal(size=(n, d)))
    x[:4] = centers[[3, 7, 5, 9]]
    return x.astype(np.float32), centers.astype(np.float32)


def _nearest(x, centers):
    d2 = ((np.asarray(x, np.float64)[:, None]
           - np.asarray(centers, np.float64)[None]) ** 2).sum(-1)
    return np.argmin(d2, axis=1), d2


def test_check_finds_a_planted_wrong_answer_and_passes_an_alias():
    x, centers = _points()
    best, d2 = _nearest(x, centers)
    assert np.all(nearest.nearest_gaps(x, best, centers, block=5) == 0.0)
    alias = best.copy()
    alias[best == 3], alias[best == 7] = 7, 3
    alias[best == 5], alias[best == 9] = 9, 5
    assert np.all(nearest.nearest_gaps(x, alias, centers, block=7) == 0.0)
    wrong = best.copy()
    wrong[10] = np.argsort(d2[10])[-1]             # the farthest center
    gaps = nearest.nearest_gaps(x, wrong, centers)
    assert np.count_nonzero(gaps) == 1 and gaps[10] > 0.1
    want = (d2[10, wrong[10]] - d2[10, best[10]]) / (
        (np.float64(x[10]) ** 2).sum()
        + (np.float64(centers[best[10]]) ** 2).sum())
    assert gaps[10] == pytest.approx(want, rel=1e-9)


@pytest.mark.parametrize("block", [1, 13, 64, 8192])
def test_check_agrees_with_assignment_gaps_at_c64(block):
    # duplicates only: `assignment_gaps` measures an alias at its first
    # point, the check at itself
    x, centers = _points(seed=1, alias=0.0)
    rng = np.random.default_rng(2)
    got = rng.integers(0, len(centers), len(x))    # mostly wrong answers
    got[::3] = _nearest(x, centers)[0][::3]
    np.testing.assert_allclose(
        nearest.nearest_gaps(x, got, centers, block=block),
        assignment_gaps(x, got, centers), rtol=1e-9, atol=1e-12)


def test_work_counts_by_hand():
    # n = 2 rows, c = 3 centers, d = 4: cross term 2*2*3*4 = 48, row
    # norms 2*2*4 = 16, center norms 2*3*4 = 24, 3 per pair 3*2*3 = 18
    assert nearest.nearest_flops(2, 3, 4) == 48 + 16 + 24 + 18
    # X 2*4, centers 3*4, labels 2: 22 words of 4 bytes
    assert nearest.nearest_bytes(2, 3, 4) == 88
    n, c, d = 4096, 65536, 128
    t = nearest.nearest_least_s(n, c, d, "TPU v5 lite")
    assert t == pytest.approx(nearest.nearest_flops(n, c, d) / 197e12)


def _run(obs=None, devices=None, window_ns=1e9):
    trace = tr.Trace(0.0, window_ns, devices or {}, [])
    cfg = {"clusters": 65536, "features": 128}
    return types.SimpleNamespace(
        cell=types.SimpleNamespace(config=cfg), record={"obs": obs}
        if obs is not None else {}, trace=trace, device_kind="TPU v5 lite")


def _busy(ms_each, count):
    return {"/device:TPU:0": [tr.Op("%fusion = f32[1] fusion()",
                                    2e6 * i, ms_each * 1e6)
                              for i in range(count)]}


@pytest.mark.parametrize("metric", READERS)
def test_readers_are_silent_where_the_run_has_nothing(metric):
    read = metric_reader(metric)
    assert read(_run()) is None                                # no counters
    parent = {"records": 8192, "batches": 2, "assign_s": 0.0,
              "assign_n": 2}                                   # no "tiled"
    assert read(_run(parent)) is None        # a CPU trace: no device ops
    if metric != "tiled_share.serve":        # the parent's work reads
        assert read(_run(parent, _busy(1.0, 2))) is not None
    assert read(_run(dict(parent, batches=0, tiled=0))) is None


def test_readers_read_what_they_say():
    obs = {"records": 3 * 4096, "batches": 3, "tiled": 2}
    run_ = _run(obs, _busy(1.5, 3))
    assert metric_reader("nearest_ms.serve")(run_) == pytest.approx(1.5)
    assert metric_reader("tiled_share.serve")(run_) == pytest.approx(
        200 / 3)
    least = 3 * nearest.nearest_flops(4096, 65536, 128) / 197e12
    assert metric_reader("nearest_roofline.serve")(run_) == pytest.approx(
        100 * least / 4.5e-3)


def test_pool_keeps_the_mixtures_law():
    from bench.kinds.serve_nearest import make_pool

    cfg = {"features": 8, "mixture": {"kind": "uniform_classes",
                                      "classes": 16, "sep": 4.0,
                                      "spread": 1.0, "means_key": 0}}
    n = 64_000
    x = np.asarray(make_pool(cfg, SEED, n), np.float64)
    _, _, means = make_mixture(dict(cfg, rows=n), jax.random.fold_in(
        seed_key(SEED), DATA_KEY))
    lab, d2 = _nearest(x, means)
    freq = np.bincount(lab, minlength=16) / n
    assert np.max(np.abs(freq - 1 / 16)) < 0.01
    resid = x - np.asarray(means, np.float64)[lab]
    assert resid.std() == pytest.approx(1.0, abs=0.05)
    again = np.asarray(make_pool(cfg, SEED, n))
    assert np.array_equal(x, again)
    with pytest.raises(ValueError):
        make_pool(dict(cfg, mixture=dict(cfg["mixture"],
                                         kind="zipf_classes")), SEED, 8)


def _small_cell() -> Cell:
    """The cell's files at a CPU's size: 1,024 centers of the cell's
    width, and a light load."""
    cell = load_cell(CELL)
    cfg = dict(cell.config, clusters=1024,
               mixture=dict(cell.config["mixture"], classes=1024))
    traffic = dict(cell.traffic, rate_per_s=100, rows_max=256)
    return cell._replace(config=cfg, traffic=traffic)


def test_cell_files_hold_the_deployment():
    cell = load_cell(CELL)
    with open(os.path.join(BENCH, "traffic", "ingest-over.json")) as f:
        assert json.load(f) == cell.traffic
    assert cell.traffic["kind"] == "serve_nearest"
    assert (cell.config["clusters"], cell.config["features"]) == (65536,
                                                                  128)
    assert cell.config["precision"] == "highest"
    assert set(cell.limits) == {"assignment_gap", "unanswered"}
    assert {m["name"] for m in cell.per_layer} >= set(READERS)


@pytest.mark.parametrize("trace", [False, True])
def test_traffic_runs_on_the_cpu(trace):
    c = _small_cell()
    out = run.run_cell(c, SEED, 1.0, trace, jax.devices(), rows=16_384)
    json.dumps(out)
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["checks"]) == set(c.limits)
    got = set(out["metrics"])
    if not trace:
        assert got == {m["name"] for m in c.end_to_end}
    else:         # a CPU trace holds no device ops: those are silent
        assert "tiled_share.serve" in got
        assert out["metrics"]["tiled_share.serve"]["value"] == 0.0
        assert not any(m["source"] == "device_trace" and m["name"] in got
                       for m in c.per_layer)


def test_altered_answers_are_found(monkeypatch):
    import repro.serve

    class Altered(repro.serve.Scorer):
        def score(self, x, snap=None):      # one answer a batch moved
            out = super().score(x, snap)
            c = (snap or self.read()).centers.shape[0]
            return out.at[0].set((out[0] + 1) % c)

    monkeypatch.setattr(repro.serve, "Scorer", Altered)
    out = run.run_cell(_small_cell(), SEED, 1.0, False, jax.devices(),
                       rows=16_384)
    assert out["correct"] is False, out["checks"]
    assert out["checks"]["unanswered"]["value"] == 0
