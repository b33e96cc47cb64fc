"""The harness on the CPU at a tiny size: the device generators against
`repro.data.synth`, the reference against the program's jnp sweep, each
traffic's set-up, window and check in interpret mode, and the refusal
to run without a TPU."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src"),
                os.path.join(ROOT, "bench")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench.harness.cell import BENCH, Cell, load_cell  # noqa: E402
from bench.harness.data import class_weights, make_mixture, \
    seed_key  # noqa: E402
from bench.harness.reference import assignment_gaps, \
    reference_sweep  # noqa: E402

import run  # noqa: E402  (bench/run.py)

SEED = 2**33 + 11          # larger than 32 bits, as the driver's are


def _stats(x, labels, c):
    x, labels = np.asarray(x, np.float64), np.asarray(labels)
    freq = np.bincount(labels, minlength=c) / len(labels)
    means = np.stack([x[labels == k].mean(0) for k in range(c)
                      if (labels == k).sum() > 50])
    resid = np.concatenate([x[labels == k] - x[labels == k].mean(0)
                            for k in range(c) if (labels == k).sum() > 50])
    return freq, float(np.sqrt((means ** 2).mean())), float(resid.std())


@pytest.mark.parametrize("config,synth", [("kdd99", "make_kdd_like"),
                                          ("higgs", "make_higgs_like")])
def test_device_generators_match_synth(config, synth):
    from repro.data import synth as program

    with open(os.path.join(BENCH, "configs", config + ".json")) as f:
        cfg = json.load(f)
    n, c = 200_000, cfg["mixture"]["classes"]
    x, lab, _ = make_mixture(cfg, seed_key(SEED), rows=n)
    assert x.shape == (n, cfg["features"]) and x.dtype == jnp.float32
    fd, sep_d, spread_d = _stats(x, lab, c)
    xs, ls = getattr(program, synth)(n, seed=3)
    if config == "higgs":      # synth's labels there are signal/background
        from repro.data.synth import make_blobs
        xs, ls = make_blobs(n, 28, 4, spread=1.0, sep=4.0, seed=3)
    fs, sep_s, spread_s = _stats(xs, ls, c)
    w = class_weights(cfg["mixture"])
    assert np.max(np.abs(fd - w)) < 0.01
    assert np.max(np.abs(fd - fs)) < 0.03      # synth samples 4096 draws
    assert spread_d == pytest.approx(cfg["mixture"]["spread"], rel=0.02)
    assert spread_d == pytest.approx(spread_s, rel=0.02)
    assert sep_d == pytest.approx(cfg["mixture"]["sep"], rel=0.25)
    again, _, _ = make_mixture(cfg, seed_key(SEED), rows=n)
    assert np.array_equal(np.asarray(x), np.asarray(again))
    other, _, _ = make_mixture(cfg, seed_key(SEED + 2**32), rows=n)
    assert not np.array_equal(np.asarray(x), np.asarray(other))


def test_reference_agrees_with_the_jnp_sweep():
    from repro.engine.backend import fcm_accumulate

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(5000, 41)).astype(np.float32) * 4)
    w = jnp.asarray(rng.uniform(0.5, 2, 5000).astype(np.float32))
    v = x[:23] + 0.1
    got = reference_sweep(x, w, v, 2.0, block=1024)
    want = fcm_accumulate(x, w, v, 2.0)
    for g, e in zip(got, want):
        g, e = np.asarray(g, np.float64), np.asarray(e, np.float64)
        assert np.max(np.abs(g - e)) <= 1e-4 * np.max(np.abs(e))


def test_assignment_gaps():
    c = np.array([[0.0, 0.0], [10.0, 0.0], [10.0, 1e-4]])   # 1, 2 alias
    x = np.array([[1.0, 0.0], [9.0, 0.0], [9.0, 0.0], [4.0, 0.0]])
    gaps = assignment_gaps(x, np.array([0, 2, 1, 1]), c)
    assert gaps[:3].tolist() == [0.0, 0.0, 0.0]
    assert gaps[3] == pytest.approx((36 - 16) / (16 + 0))


# The fit kind runs no cell of BENCHMARK.json yet (PERF.md, Open
# questions); its files are run here as a cell with limits of the test's
# own and the fit's per-layer readers.
FIT_LIMITS = {"objective_gap": 0.1, "center_shift": 1.0, "mass_rel": 1.0}
FIT_LAYER = [{"name": n, "unit": u, "source": s} for n, u, s in (
    ("compile_s.fit", "s", "host_clock"),
    ("driver_s.fit", "s", "program_span"),
    ("combiner_iters.fit", "iters", "program_counter"),
    ("reducer_iters.fit", "iters", "program_counter"),
    ("sweep_kernel_ms.fit", "ms", "device_trace"),
    ("sweep_roofline.fit", "%", "device_trace"),
    ("device_idle.fit", "%", "device_trace"))]


def _read(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def fit_cell(config: str) -> Cell:
    return Cell(f"{config}.fit", 1, _read("configs", config + ".json"),
                _read("traffic", "fit.json"), FIT_LIMITS,
                [{"name": "fit_s", "unit": "s"},
                 {"name": "setup_s", "unit": "s"}], FIT_LAYER)


# The HIGGS membership mix has no cell either (PERF.md, Open questions):
# its limit here lies between the CPU's sound reading (about 1e-6) and
# its control's (about 4e-5).
def soft_cell() -> Cell:
    return Cell("higgs.serve-soft-over", 1, _read("configs", "higgs.json"),
                _read("traffic", "serve-soft-over.json"),
                {"membership_gap": 6e-6, "unanswered": 0},
                [{"name": "score_records_per_s", "unit": "records/s"},
                 {"name": "setup_s", "unit": "s"}], [])


@pytest.mark.parametrize("cell,rows,trace", [
    ("kdd99.fit", 4096, False), ("kdd99.fit", 4096, True),
    ("higgs.fit", 4096, False), ("kdd99.serve-over", 65536, False),
    ("kdd99.serve-over", 65536, True), ("higgs.serve-soft-over", 65536, False)])
def test_traffic_runs_in_interpret_mode(cell, rows, trace):
    c = (fit_cell(cell.split(".")[0]) if cell.endswith(".fit")
         else soft_cell() if cell == "higgs.serve-soft-over"
         else load_cell(cell))
    out = run.run_cell(c, SEED, 1.0, trace, jax.devices(), rows=rows)
    json.dumps(out)
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert set(out["checks"]) == set(c.limits)
    assert out["device"]["platform"] == "cpu"
    want = (c.per_layer if trace else c.end_to_end)
    got = set(out["metrics"])
    assert got <= {m["name"] for m in want}
    if not trace:
        assert got == {m["name"] for m in want}
    else:         # a CPU trace holds no device ops: those metrics are silent
        assert not any(m["source"] == "device_trace" and m["name"] in got
                       for m in want)
        assert {m["name"] for m in want if m["source"] != "device_trace"
                and m["name"] != "driver_s.fit"} <= got
        assert "breakdown" in out and "busy_s" in out["device"]
    for m in out["metrics"].values():
        assert np.isfinite(m["value"])


def test_traffic_kind_is_found_by_module_name():
    assert run.traffic_kind("serve").__module__ == "bench.kinds.serve"
    assert run.traffic_kind("fit").__module__ == "bench.kinds.fit"
    with pytest.raises(ModuleNotFoundError):
        run.traffic_kind("no_such_kind")
    with pytest.raises(ValueError):
        run.traffic_kind("../fit")


@pytest.mark.parametrize("bursts", [False, True])
def test_serve_schedule_keeps_its_rate_and_bursts(bursts):
    from bench.kinds.serve import Traffic

    t = {"rate_per_s": 1000, "rows_min": 1, "rows_max": 4096}
    if bursts:
        t.update(burst_on_s=0.25, burst_off_s=0.75)
    me = Traffic.__new__(Traffic)
    me.traffic, me.pool = t, np.zeros((8192, 2), np.float32)
    me.rng = np.random.default_rng(SEED)
    due, sizes, offsets = me._schedule(4.0)
    assert len(due) == 4000 and np.all(np.diff(due) >= 0)
    assert due[-1] < 4.0 and 1 <= sizes.min() and sizes.max() <= 4096
    assert np.all(offsets + sizes <= 8192)
    phase = np.mod(due, 1.0)
    assert np.all(phase < 0.25) if bursts else np.mean(phase >= 0.25) > 0.7
    me.rng = np.random.default_rng(SEED + 1)
    again = me._schedule(4.0)
    assert np.array_equal(np.sort(sizes), np.sort(again[1]))


def test_run_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", "kdd99.serve-over", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=300, cwd=ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr
