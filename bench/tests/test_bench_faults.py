"""Each cell's check finds out a broken timed path, on the CPU at a tiny
size: the harness runs as on the chip, past its look for a chip, with
the program broken underneath, and `correct` comes out false.  The
serving control (the reference at ``HIGH`` in the scorer's place) is
found out too.  The fit kind, which runs no cell yet, is held to its
own faults with the test limits of `test_bench_harness`."""
import dataclasses
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src"),
                os.path.join(ROOT, "bench")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import repro.core  # noqa: E402
import repro.serve  # noqa: E402
from bench.harness.cell import load_cell  # noqa: E402

import run  # noqa: E402  (bench/run.py)
from test_bench_harness import fit_cell, soft_cell  # noqa: E402

SEED = 2**32 + 5
REAL_FIT = repro.core.bigfcm_fit


def _unchanged(x, cfg, **kw):       # every loop returns its initial state
    return REAL_FIT(x, dataclasses.replace(cfg, max_iter=0), **kw)


def _altered(x, cfg, **kw):         # an answer altered where it is made:
    res = REAL_FIT(x, cfg, **kw)    # the features written out in reverse
    return res._replace(centers=res.centers[:, ::-1])


def _half(x, cfg, **kw):            # half of the rows left out
    return REAL_FIT(x[: x.shape[0] // 2], cfg, **kw)


# The half-rows fault reads on the masses; the one-chip KDD fit's
# reducer self-polish moves its masses by up to a third on sound runs
# (PERF.md), so that fault is held to the HIGGS files alone.
@pytest.mark.parametrize("cell,fault", [
    ("kdd99", None), ("kdd99", _unchanged), ("kdd99", _altered),
    ("higgs", None), ("higgs", _unchanged), ("higgs", _altered),
    ("higgs", _half)])
def test_fit_faults_are_found(monkeypatch, cell, fault):
    if fault is not None:
        monkeypatch.setattr(repro.core, "bigfcm_fit", fault)
    c = fit_cell(cell)
    if cell == "higgs":
        c = c._replace(limits=dict(c.limits, mass_rel=0.1))
    out = run.run_cell(c, SEED, 0.5, False, jax.devices(), rows=4096)
    assert out["correct"] is (fault is None), out["checks"]


class _AlteredScorer(repro.serve.Scorer):
    def score(self, x, snap=None):          # one answer altered
        out = super().score(x, snap)
        if self.soft:                       # memberships: a row's rolled
            return out.at[0].set(jnp.roll(out[0], 1))
        c = (snap or self.read()).centers.shape[0]
        return out.at[0].set((out[0] + 1) % c)


class _HalfScorer(repro.serve.Scorer):
    def score(self, x, snap=None):          # half of the batch left out
        out = super().score(x[: (x.shape[0] + 1) // 2], snap)
        rest = jnp.zeros((x.shape[0] - out.shape[0],) + out.shape[1:],
                         out.dtype)
        return jnp.concatenate([out, rest])


class _UnchangedScorer(repro.serve.Scorer):
    def score(self, x, snap=None):          # the answers' initial state
        return jnp.zeros(x.shape[0], jnp.int32)


@pytest.mark.parametrize("cell,scorer", [
    ("kdd99.serve-over", "altered"), ("kdd99.serve-over", "half"),
    ("kdd99.serve-over", "unchanged"), ("kdd99.serve-over", "control"),
    ("higgs.serve-soft-over", "altered"), ("higgs.serve-soft-over", "half"),
    ("higgs.serve-soft-over", "control")])
def test_serve_faults_and_control_are_found(monkeypatch, cell, scorer):
    from bench.harness.control import ControlScorer

    monkeypatch.setattr(repro.serve, "Scorer", {
        "altered": _AlteredScorer, "half": _HalfScorer,
        "unchanged": _UnchangedScorer, "control": ControlScorer}[scorer])
    c = soft_cell() if cell == "higgs.serve-soft-over" else load_cell(cell)
    out = run.run_cell(c, SEED, 2.0, False, jax.devices(), rows=262_144)
    assert out["correct"] is False, out["checks"]
    assert out["checks"]["unanswered"]["value"] == 0
