"""Pallas kernel vs pure-jnp oracle: shape/dtype sweep (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.fcm_update import fcm_accumulate_pallas, fcm_sweep_pallas
from repro.kernels.ops import (accumulate_chunks, fcm_accumulate_kernel,
                               fcm_sweep_kernel)
from repro.kernels.ref import fcm_accumulate_ref, fcm_sweep_ref

SHAPES = [
    (64, 2, 2), (100, 130, 7), (257, 4, 3), (1000, 18, 10),
    (2048, 28, 50), (31, 41, 23), (512, 8, 129),
]

# C and d above the 128 MXU lane but NOT multiples of it — both the
# center and feature axes get zero-padded and the phantom centers must
# be masked out of the membership denominator.
OFF_LANE_SHAPES = [
    (300, 130, 131), (200, 129, 140), (96, 257, 129), (513, 131, 200),
]


@pytest.mark.parametrize("n,d,c", SHAPES)
def test_kernel_matches_ref_shapes(n, d, c):
    rng = np.random.default_rng(n + d + c)
    x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    w = jnp.asarray(rng.uniform(0.1, 3.0, size=(n,)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(c, d)).astype(np.float32))
    got = fcm_sweep_kernel(x, w, v, 2.0)
    want = fcm_sweep_ref(x, w, v, 2.0)
    for g, e in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(e),
                                   rtol=3e-4, atol=3e-5)


@pytest.mark.parametrize("m", [1.2, 2.0, 3.0])
def test_kernel_matches_ref_m(m):
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(500, 12)).astype(np.float32))
    w = jnp.ones((500,), jnp.float32)
    v = jnp.asarray(rng.normal(size=(6, 12)).astype(np.float32))
    got = fcm_sweep_kernel(x, w, v, m)
    want = fcm_sweep_ref(x, w, v, m)
    for g, e in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(e),
                                   rtol=5e-4, atol=5e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kernel_dtypes(dtype):
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(256, 16)), dtype)
    w = jnp.ones((256,), jnp.float32)
    v = jnp.asarray(rng.normal(size=(4, 16)), dtype)
    got = fcm_sweep_kernel(x, w, v, 2.0)
    want = fcm_sweep_ref(x, w, v, 2.0)
    tol = 3e-4 if dtype == jnp.float32 else 3e-2
    for g, e in zip(got, want):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(e, np.float32),
                                   rtol=tol, atol=tol)


@pytest.mark.parametrize("tile_n", [128, 512, 1024])
def test_kernel_tile_invariance(tile_n):
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(1111, 9)).astype(np.float32))
    w = jnp.asarray(rng.uniform(0.5, 1.5, size=(1111,)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(5, 9)).astype(np.float32))
    got = fcm_sweep_pallas(x, w, v, 2.0, tile_n=tile_n, interpret=True)
    want = fcm_sweep_ref(x, w, v, 2.0)
    for g, e in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(e),
                                   rtol=3e-4, atol=3e-5)


@pytest.mark.parametrize("n,d,c", OFF_LANE_SHAPES)
def test_kernel_phantom_masking_off_lane_shapes(n, d, c):
    """Parity where C and d are not multiples of 128 (phantom-center
    masking + feature-axis padding both in play)."""
    rng = np.random.default_rng(n * 7 + d + c)
    x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    w = jnp.asarray(rng.uniform(0.1, 3.0, size=(n,)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(c, d)).astype(np.float32))
    got = fcm_sweep_kernel(x, w, v, 2.0)
    want = fcm_sweep_ref(x, w, v, 2.0)
    for g, e in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(e),
                                   rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("n,d,c", [(300, 13, 6), (257, 130, 131)])
def test_accumulate_matches_ref(n, d, c):
    rng = np.random.default_rng(n + d + c)
    x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    w = jnp.asarray(rng.uniform(0.1, 2.0, size=(n,)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(c, d)).astype(np.float32))
    got = fcm_accumulate_kernel(x, w, v, 2.0)
    want = fcm_accumulate_ref(x, w, v, 2.0)
    for g, e in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(e),
                                   rtol=3e-4, atol=3e-3)


def test_accumulate_chunks_equals_single_sweep():
    """The streaming property: raw accumulators from chunk slices sum to
    the whole — one normalization at the end equals one full sweep."""
    rng = np.random.default_rng(17)
    x = jnp.asarray(rng.normal(size=(900, 11)).astype(np.float32))
    w = jnp.asarray(rng.uniform(0.5, 1.5, size=(900,)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(5, 11)).astype(np.float32))
    cuts = [0, 250, 600, 900]
    chunks = [x[a:b] for a, b in zip(cuts, cuts[1:])]
    ws = [w[a:b] for a, b in zip(cuts, cuts[1:])]
    got = accumulate_chunks(chunks, ws, v, 2.0)
    want = fcm_sweep_kernel(x, w, v, 2.0)
    for g, e in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(e),
                                   rtol=1e-5, atol=1e-5)


def test_kernel_inside_full_fcm_loop():
    from repro.core import fcm
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.normal(size=(600, 8)).astype(np.float32))
    # f32 oracle reference: "auto" may pick the bf16 backend (PR 6),
    # which legitimately converges in a different iteration count
    r_ref = fcm(x, x[:5], m=2.0, eps=1e-8, max_iter=100, backend="jnp")
    r_k = fcm(x, x[:5], m=2.0, eps=1e-8, max_iter=100, backend="pallas")
    assert int(r_ref.n_iter) == int(r_k.n_iter)
    np.testing.assert_allclose(np.asarray(r_ref.centers),
                               np.asarray(r_k.centers), rtol=2e-3,
                               atol=2e-4)


# -------------------------------------------- nearest-center assignment ---

from repro.engine import get_backend, hard_assign  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.kernels.nearest import nearest_center_pallas  # noqa: E402
from repro.kernels.ref import (nearest_center_ref,  # noqa: E402
                               nearest_center_right)


@pytest.mark.parametrize("n,c,d,tiles", [
    (777, 1000, 41, {}),                  # ragged N, C off the C tile
    (777, 1000, 128, {}),
    (300, 2048, 128, {"tile_n": 128, "tile_c": 256}),   # 3 x 8 grid
    (1500, 700, 41, {"tile_n": 1024, "tile_c": 512}),   # ragged both
    (64, 23, 41, {}),                     # the KDD shape
])
def test_nearest_kernel_matches_float64_reference(n, c, d, tiles):
    rng = np.random.default_rng(n + c + d)
    x = rng.normal(size=(n, d)).astype(np.float32)
    v = (rng.normal(size=(c, d)) * 0.5).astype(np.float32)
    got = np.asarray(nearest_center_pallas(x, v, interpret=True, **tiles))
    assert got.shape == (n,) and got.dtype == np.int32
    assert nearest_center_right(x, v, got).all()
    want, _ = nearest_center_ref(x, v)
    np.testing.assert_array_equal(got, want)
    # the same answers as the jnp path it replaces at large C
    np.testing.assert_array_equal(got, np.asarray(hard_assign(x, v)))


def test_nearest_kernel_duplicates_and_ties_go_to_lowest_index():
    """Duplicate centers and exact ties, across sublanes and C tiles:
    the lowest index wins, as `jnp.argmin` gives."""
    d, c = 41, 200
    rng = np.random.default_rng(3)
    v = rng.normal(size=(c, d)).astype(np.float32) + 5.0
    v[77] = v[9]                  # duplicates in one tile, other sublane
    v[150] = v[9]                 # and in a later C tile
    e = np.zeros(d, np.float32)
    e[0] = 1.0
    v[20], v[140] = e, -e         # equidistant from the origin
    x = np.zeros((50, d), np.float32)
    x[:10] = v[9]                 # on the duplicated center
    x[10:20] = v[150]
    # rows 20..49 sit at the origin: d2 = 1 to centers 20 and 140 exactly
    got = np.asarray(nearest_center_pallas(x, v, interpret=True,
                                           tile_n=128, tile_c=64))
    assert (got[:20] == 9).all()
    assert (got[20:] == 20).all()
    np.testing.assert_array_equal(got, np.asarray(hard_assign(x, v)))
    want, _ = nearest_center_ref(x, v)
    np.testing.assert_array_equal(got, want)
    # the reference's rule: an alias of the nearest center is right
    alias = got.copy()
    alias[:10] = 150
    assert nearest_center_right(x, v, alias).all()
    alias[0] = 140
    assert not nearest_center_right(x, v, alias)[0]


def test_nearest_by_kernel_shape_rule():
    """The C-tiled kernel at `NEAREST_MIN_CENTERS` centers and above,
    on a TPU only; the jnp path everywhere else."""
    k = ops.NEAREST_MIN_CENTERS
    assert ops.nearest_by_kernel(k, tpu=True)
    assert ops.nearest_by_kernel(65_536, tpu=True)
    assert not ops.nearest_by_kernel(k - 1, tpu=True)
    assert not ops.nearest_by_kernel(23, tpu=True)
    assert not ops.nearest_by_kernel(65_536, tpu=False)
    # this process is off the TPU: the backend keeps the jnp path
    pallas = get_backend("pallas")
    assert not pallas.assigns_by_kernel(65_536)
    assert not get_backend("jnp").assigns_by_kernel(65_536)
