"""Compile rehearsal: the Pallas kernels of the served path, at the
widths the repo runs in production, compiled for a described (not
attached) TPU v5e by the TPU compiler that ships with jax.

Interpret mode cannot show what these do: the compiler refuses scalar
stores to VMEM, blocks that do not divide the (8, 128) tiling, DMA
slices narrower than a 128-lane tile, and programs larger than HBM.
Each test compiles one kernel (or the fused driver) and asserts the
kernel is in the compiled program (``tpu_custom_call``). Nothing runs.

The topology is described inside a module fixture (never while a
module is imported): only one process may load the TPU library, and
under pytest-xdist the other workers must still collect these tests.
The persistent compilation cache is off while they compile — an entry
written for a described chip cannot be read back without one.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.backends import FusedBackend
from repro.core.delta_stepping import _run_many_seq
from repro.graphs.structures import ELLGraph
from repro.kernels.bucket_scan import bucket_scan
from repro.kernels.ell_relax import ell_relax
from repro.kernels.frontier_relax import frontier_relax
from repro.kernels.grid_relax import grid_relax

V5E_HBM = 16 * 10**9
N_1M = 1 << 20


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # no compiler logs in /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means no TPU lib
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_bucket_scan_compiles_at_2_21(one_chip):
    n = 1 << 21
    _compile(lambda t, e, i: bucket_scan(t, e, i, delta=10),
             _spec(one_chip, (n,)), _spec(one_chip, (n,)),
             _spec(one_chip, ()))


def test_ell_relax_blocked_compiles(one_chip):
    _compile(lambda f, d, w: ell_relax(f, d, w),
             _spec(one_chip, (4096,)), _spec(one_chip, (N_1M,)),
             _spec(one_chip, (N_1M + 1, 16)))


@pytest.mark.parametrize("width", [60, 128])
def test_frontier_relax_compiles_1m_rows(one_chip, width):
    """SSSP_SMALLWORLD's degree 60 as given (the wrapper pads to whole
    lane tiles) and as the fused backends lay it out (pre-padded)."""
    n = N_1M
    _compile(lambda d, e, i, nb, w: frontier_relax(d, e, i, nb, w,
                                                   delta=10, cap=n),
             _spec(one_chip, (n,)), _spec(one_chip, (n,)),
             _spec(one_chip, ()), _spec(one_chip, (n + 1, width)),
             _spec(one_chip, (n + 1, width)))


@pytest.mark.parametrize("light", [True, False])
def test_grid_relax_compiles_3000x3000(one_chip, light):
    side = 3000
    _compile(lambda t, f, i: grid_relax(t, f, i, light=light),
             _spec(one_chip, (side, side)),
             _spec(one_chip, (side, side), jnp.bool_), _spec(one_chip, ()))


def test_fused_driver_fits_one_chip_at_1m(one_chip):
    """The whole ``fused`` solve program (kernel + XLA scatter glue) of
    a 2-lane batch on the 1M-vertex, degree-60 small-world graph fits a
    v5e's HBM."""
    n, light_w, heavy_w = N_1M, 128, 50
    light = ELLGraph(_spec(one_chip, (n + 1, light_w)),
                     _spec(one_chip, (n + 1, light_w)), n, light_w)
    heavy = ELLGraph(_spec(one_chip, (n + 1, heavy_w)),
                     _spec(one_chip, (n + 1, heavy_w)), n, heavy_w)
    backend = FusedBackend(light, heavy, 10, n, n, "compiled", 60, True)
    compiled = _run_many_seq.lower(backend, _spec(one_chip, (2,)), n=n,
                                   packed=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < V5E_HBM, used
