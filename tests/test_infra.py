"""Substrate tests: data pipeline, checkpointing, optimizers, schedules,
straggler monitor, metrics."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.metrics import clustering_accuracy, silhouette_width
from repro.data import ShardedLoader, iris, make_kdd_like, pima_like
from repro.data.loader import normalize, parse_records
from repro.ft import CheckpointManager, StragglerMonitor
from repro.optim import (adafactor, adamw, clip_by_global_norm,
                         cosine_schedule, global_norm)


def test_parse_and_normalize():
    x = parse_records(["1.0, 2.0, 3.0", " 4 ,5,6 ", ""])
    assert x.shape == (2, 3)
    n = normalize(x)
    assert n.min() == 0.0 and n.max() == 1.0


def test_sharded_loader_pads_tail_with_zero_weights():
    chunks = iter([np.ones((70, 3), np.float32)])
    loader = ShardedLoader(chunks, batch_rows=32)
    batches = list(loader)
    assert len(batches) == 3
    x, w = batches[-1]
    assert x.shape == (32, 3)
    assert float(w.sum()) == 6.0  # 70 - 64 real rows


def test_iris_embedded():
    x, y = iris()
    assert x.shape == (150, 4) and y.shape == (150,)
    assert np.bincount(y).tolist() == [50, 50, 50]


def test_kdd_like_imbalanced():
    x, y = make_kdd_like(5000)
    assert x.shape == (5000, 41)
    counts = np.bincount(y, minlength=23)
    assert counts.max() > 5 * max(counts[counts > 0].min(), 1)


def test_clustering_accuracy_perfect_and_permuted():
    y = np.array([0, 0, 1, 1, 2, 2])
    a = np.array([2, 2, 0, 0, 1, 1])
    assert clustering_accuracy(y, a, 3) == 1.0


def test_silhouette_range():
    x, y = pima_like(300)
    s = silhouette_width(x, y, max_points=300)
    assert -1.0 <= s <= 1.0


def test_checkpoint_atomic_keep_and_resume():
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d, keep=2, async_save=False)
        tree = {"a": jnp.arange(5, dtype=jnp.float32),
                "b": {"c": jnp.ones((2, 2))}}
        for s in (1, 2, 3):
            mgr.save(s, jax.tree_util.tree_map(lambda x: x * s, tree))
        assert mgr.all_steps() == [2, 3]
        got = mgr.restore(tree)
        np.testing.assert_allclose(np.asarray(got["a"]),
                                   np.arange(5, dtype=np.float32) * 3)
        # no stray tmp dirs
        assert not [f for f in os.listdir(d) if f.endswith(".tmp")]


def test_clip_by_global_norm():
    g = {"w": jnp.full((4,), 10.0)}
    clipped, norm = clip_by_global_norm(g, 1.0)
    assert float(norm) == pytest.approx(20.0)
    assert float(global_norm(clipped)) == pytest.approx(1.0, rel=1e-5)


def test_cosine_schedule_shape():
    lr0 = float(cosine_schedule(0, peak=1.0, warmup=10, total=100))
    lr_peak = float(cosine_schedule(10, peak=1.0, warmup=10, total=100))
    lr_end = float(cosine_schedule(100, peak=1.0, warmup=10, total=100))
    assert lr0 < lr_peak
    assert lr_end == pytest.approx(0.1, rel=1e-3)


@pytest.mark.parametrize("optname,opt", [("adamw", adamw()),
                                         ("adafactor", adafactor())])
def test_optimizers_reduce_quadratic(optname, opt):
    params = {"w": jnp.asarray([3.0, -2.0])}
    state = opt.init(params)

    @jax.jit
    def step(p, s):
        g = jax.grad(lambda q: jnp.sum(q["w"] ** 2))(p)
        return opt.update(g, s, p, 0.1)

    for _ in range(50):
        params, state = step(params, state)
    assert float(jnp.abs(params["w"]).max()) < 1.0


def test_straggler_monitor_flags_outlier():
    mon = StragglerMonitor(threshold=1.5, min_samples=2)
    import time
    for i in range(10):
        mon.start()
        time.sleep(0.02 if i != 7 else 0.08)
        flagged = mon.stop()
        if i == 7:
            assert flagged
    assert mon.flags == 1


_CACHE_PROBE = """
import json, os, sys, uuid
sys.path.insert(0, {src!r})
import jax, jax.numpy as jnp
from repro.launch.cache import REPO_ROOT, enable_compile_cache

def count(d):
    return sum(len(f) for _, _, f in os.walk(d)) if os.path.isdir(d) else 0

repo_dir = os.path.join(REPO_ROOT, ".cache", "jax")
env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
before = {{"repo": count(repo_dir), "env": count(env_dir or "")}}
path = enable_compile_cache()
salt = uuid.uuid4().int % 1000003          # a program no run compiled yet
jax.block_until_ready(jax.jit(lambda a: a * 2.0 + salt)(jnp.ones(8)))
print(json.dumps({{"path": path,
                  "config": jax.config.jax_compilation_cache_dir,
                  "repo": count(repo_dir) - before["repo"],
                  "env": count(env_dir or "") - before["env"]}}))
"""


@pytest.mark.parametrize("env_set", [True, False])
def test_compile_cache_goes_where_the_environment_says(tmp_path, env_set):
    """With ``JAX_COMPILATION_CACHE_DIR`` set, compiled entries land only
    there; without it, only under ``<repo>/.cache/jax``."""
    import json
    import subprocess
    import sys

    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_set:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    out = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE.format(
            src=os.path.abspath(src))],
        capture_output=True, text=True, timeout=300, env=env,
        cwd=str(tmp_path))
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    repo_dir = os.path.join(os.path.dirname(os.path.abspath(src)),
                            ".cache", "jax")
    want = str(tmp_path) if env_set else repo_dir
    assert os.path.abspath(rec["path"]) == want
    assert os.path.abspath(rec["config"]) == want
    if env_set:
        assert rec["env"] > 0 and rec["repo"] == 0, rec
    else:
        assert rec["repo"] > 0, rec
