"""Compile the main path for a TPU v5e without one.

The TPU compiler is installed with jax, and it compiles for a chip that
is described, not attached: a ``v5e:2x2`` topology.  These compiles
catch what interpret mode cannot (Mosaic refusing a kernel's tiling or
its VMEM use, a program that does not fit HBM, a collective that
cannot be partitioned) at no chip time.  Nothing here runs.

Only one process at a time may load the TPU library, and it keeps it
until it exits.  So the topology is described inside a module-scoped
fixture, never while a module is imported, and every test that needs
it lives in this one file.  Code that asks `jax.default_backend()`
still sees the CPU here, so the tests steer the kernel wrapper off
interpret mode themselves.
"""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import BigFCMConfig, fcm
from repro.core.bigfcm import mesh_job
from repro.engine import get_backend
from repro.kernels import ops
from repro.data.plane import shape_buckets
from repro.kernels.fcm_update import fcm_accumulate_pallas
from repro.kernels.nearest import nearest_center_pallas
from repro.perf import calibrate
from repro.serve import ServiceConfig

HBM_BYTES = 16 * 2**30          # one v5e chip
KDD = (4_898_431, 41, 23)       # rows, features, clusters (paper Table 3)
HIGGS = (11_000_000, 28, 2)
IVF = (65_536, 128)             # coarse centers, SIFT width
_CFG = ServiceConfig()
SERVE_BUCKETS = shape_buckets(_CFG.max_batch_rows, base=_CFG.bucket_base,
                              factor=_CFG.bucket_factor)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # A compile for a described chip is written to the persistent
        # cache but cannot be read back without one: keep it out.
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def mosaic(monkeypatch, tmp_path):
    """The kernel wrapper as it runs on a TPU: compiled by Mosaic, with
    the default blocks (no autotuned entry from this host's file)."""
    monkeypatch.setattr(ops, "interpret_mode", lambda: False)
    monkeypatch.setenv(calibrate.ENV_DIR, str(tmp_path))
    calibrate.clear_memory_cache()
    yield
    calibrate.clear_memory_cache()


def _spec(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _fits_hbm(compiled) -> int:
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used <= HBM_BYTES, f"{used / 2**30:.2f} GiB > 16 GiB: {mem}"
    return used


@pytest.mark.parametrize("c,d", [(2, 18), (2, 28), (23, 41)])
def test_kernel_compiles_at_paper_widths(one_chip, c, d):
    n = 65_536
    lowered = jax.jit(partial(fcm_accumulate_pallas, m=2.0,
                              interpret=False)).lower(
        _spec((n, d), one_chip), _spec((n,), one_chip),
        _spec((c, d), one_chip))
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits_hbm(compiled)


@pytest.mark.parametrize("rows", SERVE_BUCKETS)
def test_nearest_kernel_compiles_at_ivf_width(one_chip, rows):
    """The C-tiled nearest-center kernel at 65,536 centers of d = 128,
    at every bucket of the scoring service's default ladder: Mosaic
    accepts its tiling and VMEM, and no (rows, C) array reaches HBM."""
    c, d = IVF
    compiled = jax.jit(partial(nearest_center_pallas,
                               interpret=False)).lower(
        _spec((rows, d), one_chip), _spec((c, d), one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _fits_hbm(compiled) < 2 * c * d * 4 + 8 * rows * d * 4


def test_tenant_vmapped_kernel_compiles(one_chip):
    t, n, c, d = 64, 1024, 4, 8
    acc = jax.vmap(partial(fcm_accumulate_pallas, m=2.0, interpret=False))
    compiled = jax.jit(acc).lower(
        _spec((t, n, d), one_chip), _spec((t, n), one_chip),
        _spec((t, c, d), one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_single_chip_fcm_program_fits_hbm_at_higgs_size(one_chip, mosaic):
    """The whole single-chip combiner (`core.fcm` over the ``pallas``
    backend, one XLA while loop) at the HIGGS shape fits one chip."""
    n, d, c = HIGGS
    prog = jax.jit(partial(fcm, m=2.0, eps=1e-8, max_iter=1000,
                           backend="pallas"))
    compiled = prog.lower(_spec((n, d), one_chip),
                          _spec((c, d), one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits_hbm(compiled)


@pytest.mark.parametrize("flag", [True, False], ids=["fcm", "wfcmpb"])
def test_mesh_combiner_reducer_compiles_on_four_chips(topo, mosaic, flag):
    """The `shard_map` combiner/reducer of `bigfcm_fit` on a 4-chip mesh
    at the KDD Cup 99 shape (rows padded to a multiple of 4): each chip
    runs the Pallas sweep, and the reducer gathers the summaries."""
    n, d, c = KDD
    n += -n % 4
    mesh = Mesh(np.asarray(topo.devices), ("data",))
    job = mesh_job(mesh, BigFCMConfig(n_clusters=c, backend="pallas"),
                   flag=flag, backend=get_backend("pallas"))
    rows = NamedSharding(mesh, P("data"))
    compiled = jax.jit(job).lower(
        _spec((n, d), rows), _spec((n,), rows),
        _spec((c, d), NamedSharding(mesh, P(None, None)))).compile()
    text = compiled.as_text()
    assert "all-gather" in text
    assert "tpu_custom_call" in text
    _fits_hbm(compiled)
