"""End-to-end behaviour of the paper's system (BigFCM pipeline), plus
multi-device integration via subprocess (device count must be set before
jax import, and only for these tests)."""
import json
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import BigFCMConfig, bigfcm_fit
from repro.core.metrics import assign, clustering_accuracy, silhouette_width
from repro.data import make_blobs

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_bigfcm_single_device_end_to_end():
    x, y = make_blobs(4000, 8, 4, seed=0)
    cfg = BigFCMConfig(n_clusters=4, sample_size=512)
    res = bigfcm_fit(jnp.asarray(x), cfg)
    acc = clustering_accuracy(y, assign(x, res.centers), 4)
    assert acc > 0.97
    assert res.diagnostics.sample_size == 512
    assert float(res.objective) > 0


def test_bigfcm_driver_picks_a_flag():
    x, _ = make_blobs(2000, 6, 3, seed=1)
    cfg = BigFCMConfig(n_clusters=3, sample_size=256)
    res = bigfcm_fit(jnp.asarray(x), cfg)
    assert isinstance(res.diagnostics.flag, (bool, np.bool_))
    assert res.diagnostics.t_fcm_driver > 0
    assert res.diagnostics.t_wfcmpb_driver > 0


def test_bigfcm_silhouette_positive_on_separated_blobs():
    x, _ = make_blobs(2000, 8, 4, sep=8.0, seed=2)
    cfg = BigFCMConfig(n_clusters=4, sample_size=256)
    res = bigfcm_fit(jnp.asarray(x), cfg)
    sw = silhouette_width(x, assign(x, res.centers), max_points=800)
    assert sw > 0.5


_MULTIDEV = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys, json
    sys.path.insert(0, {src!r})
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import BigFCMConfig, bigfcm_fit, fcm
    from repro.core.metrics import assign, clustering_accuracy
    from repro.data import make_blobs

    x, y = make_blobs(8192, 8, 4, seed=0)
    mesh = jax.make_mesh((2, 4), ("pod", "data"))
    cfg = BigFCMConfig(n_clusters=4, sample_size=512, hierarchical={hier})
    res = bigfcm_fit(jnp.asarray(x), cfg, mesh=mesh,
                     data_axes=("pod", "data"))
    acc = clustering_accuracy(y, assign(x, res.centers), 4)
    # distributed result must match the single-machine FCM quality
    single = fcm(jnp.asarray(x), res.centers, m=2.0, eps=1e-9, max_iter=200)
    drift = float(jnp.max(jnp.sum((single.centers - res.centers) ** 2, -1)))
    print(json.dumps({{"acc": acc, "drift": drift,
                       "iters": np.asarray(
                           res.diagnostics.combiner_iters).tolist()}}))
""")


@pytest.mark.parametrize("hier", [False, True])
def test_bigfcm_multidevice_subprocess(hier):
    code = _MULTIDEV.format(src=os.path.abspath(SRC), hier=hier)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["acc"] > 0.97, rec
    # reducer-refined centers are a fixed point of full-data FCM (≈)
    assert rec["drift"] < 0.05, rec
    assert len(rec["iters"]) == 8


def test_mr_fkm_baseline_equivalent_quality():
    from repro.baselines import mr_fuzzy_kmeans
    x, y = make_blobs(3000, 6, 3, seed=3)
    res, n_jobs, elapsed = mr_fuzzy_kmeans(jnp.asarray(x), jnp.asarray(x[:3]),
                                           m=2.0, eps=1e-9, max_iter=300)
    acc = clustering_accuracy(y, assign(x, res.centers), 3)
    assert acc > 0.97
    assert n_jobs > 1 and elapsed > 0


_MESH_PAD = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import sys, json
    sys.path.insert(0, {src!r})
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import BigFCMConfig, bigfcm_fit
    from repro.core.metrics import fuzzy_objective, match_centers
    from repro.data import make_blobs
    from repro.launch.mesh import make_host_mesh

    x, _ = make_blobs(40003, 8, 4, seed=0)   # 4 devices do not divide it
    cfg = BigFCMConfig(n_clusters=4, sample_size=512)
    mesh = make_host_mesh()
    res = bigfcm_fit(jnp.asarray(x), cfg, mesh=mesh)
    one = bigfcm_fit(jnp.asarray(x), cfg)
    q_one = float(fuzzy_objective(jnp.asarray(x), one.centers))
    print(json.dumps({{
        "devices": len(mesh.devices.flatten()),
        "center_dist": match_centers(np.asarray(res.centers),
                                     np.asarray(one.centers)),
        "q_mesh": float(res.objective), "q_one": q_one}}))
""")


def test_bigfcm_mesh_pads_rows_the_mesh_does_not_divide():
    code = _MESH_PAD.format(src=os.path.abspath(SRC))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["devices"] == 4, rec
    assert rec["center_dist"] < 1e-2, rec
    assert abs(rec["q_mesh"] - rec["q_one"]) <= 1e-4 * rec["q_one"], rec
