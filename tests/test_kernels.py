"""Per-kernel validation: Pallas (interpret=True on CPU) vs pure-jnp
oracle, swept over shapes/dtypes/parameters, plus end-to-end use of the
grid kernel inside the game-map solver against Dijkstra."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import DeltaConfig, delta_stepping, dijkstra
from repro.core.grid import GridDeltaConfig, GridDeltaSolver
from repro.graphs import grid_map
from repro.graphs.structures import INF32, coo_to_csr, csr_to_ell
from repro.graphs.generators import random_graph
from repro.kernels.bucket_scan import bucket_scan, bucket_scan_ref
from repro.kernels.ell_relax import ell_relax, ell_relax_ref
from repro.kernels.grid_relax import grid_relax, grid_relax_ref


def _rand_tent(rng, shape, frac_inf=0.3, hi=400):
    t = rng.integers(0, hi, size=shape).astype(np.int32)
    mask = rng.random(shape) < frac_inf
    return np.where(mask, INF32, t).astype(np.int32)


# ---------------------------------------------------------------- grid_relax
@pytest.mark.parametrize("shape", [(8, 16), (16, 128), (65, 130), (3, 257),
                                   (128, 128)])
@pytest.mark.parametrize("light", [True, False])
@pytest.mark.parametrize("delta", [13, 10, 25])
def test_grid_relax_matches_ref(shape, light, delta):
    rng = np.random.default_rng(hash((shape, light, delta)) % 2**32)
    tent = jnp.asarray(_rand_tent(rng, shape))
    free = jnp.asarray(rng.random(shape) > 0.2)
    tent = jnp.where(free, tent, INF32)
    for i in [0, 3, 11]:
        ref = grid_relax_ref(tent, free, i, delta=delta, cost_straight=10,
                             cost_diag=14, light=light)
        for block_rows in [8, 64]:
            out = grid_relax(tent, free, i, delta=delta, cost_straight=10,
                             cost_diag=14, light=light, block_rows=block_rows,
                             backend="pallas", interpret=True)
            np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_grid_relax_settles_single_source():
    free = jnp.ones((4, 8), bool)
    tent = jnp.full((4, 8), INF32, jnp.int32).at[0, 0].set(0)
    out = grid_relax(tent, free, 0, delta=13, cost_straight=10, cost_diag=14,
                     light=True, backend="pallas", interpret=True)
    assert int(out[0, 1]) == 10 and int(out[1, 0]) == 10
    assert int(out[1, 1]) == INF32  # diagonal is heavy under Δ=13


# ----------------------------------------------------------------- ell_relax
@pytest.mark.parametrize("n,deg,cap", [(16, 4, 8), (64, 7, 64), (33, 1, 16),
                                       (128, 16, 40)])
@pytest.mark.parametrize("backend", ["pallas"])
def test_ell_relax_matches_ref(n, deg, cap, backend):
    rng = np.random.default_rng(n * 1000 + deg)
    g = random_graph(n, n * deg, seed=int(rng.integers(2**31)))
    ell = csr_to_ell(coo_to_csr(g))
    dist = jnp.asarray(_rand_tent(rng, (n,)))
    fidx = np.full(cap, n, np.int32)
    k = rng.integers(1, cap + 1)
    fidx[:k] = rng.choice(n, size=k, replace=False)
    fidx = jnp.asarray(fidx)
    ref = ell_relax_ref(fidx, dist, ell.w)
    out = ell_relax(fidx, dist, ell.w, backend=backend, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


# --------------------------------------------------------------- bucket_scan
@pytest.mark.parametrize("n", [5, 128, 1000, 4096])
@pytest.mark.parametrize("delta", [1, 10, 64])
def test_bucket_scan_matches_ref(n, delta):
    rng = np.random.default_rng(n + delta)
    tent = jnp.asarray(_rand_tent(rng, (n,)))
    explored = jnp.asarray(_rand_tent(rng, (n,)))
    for i in [0, 2, 9]:
        f_ref, any_ref, nxt_ref = bucket_scan_ref(tent, explored, i,
                                                  delta=delta)
        f, any_, nxt = bucket_scan(tent, explored, i, delta=delta,
                                   backend="pallas", interpret=True)
        np.testing.assert_array_equal(np.asarray(f), np.asarray(f_ref))
        assert bool(any_) == bool(any_ref)
        assert int(nxt) == int(nxt_ref)


# ------------------------------------------------- end-to-end grid solver
@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_grid_solver_matches_dijkstra(backend):
    h, w = 20, 33
    g, free = grid_map(h, w, 0.15, seed=21)
    src_flat = int(np.flatnonzero(free.ravel())[0])
    dref, _ = dijkstra(g, src_flat)
    cfg = GridDeltaConfig(backend=backend, interpret=(backend == "pallas"),
                          block_rows=8)
    solver = GridDeltaSolver(free, cfg)
    res = solver.solve((src_flat // w, src_flat % w))
    np.testing.assert_array_equal(
        np.asarray(res.dist).ravel().astype(np.int64), dref)
    # cross-check with the generic edge engine too
    d2 = delta_stepping(g, src_flat, DeltaConfig(delta=13)).dist
    np.testing.assert_array_equal(np.asarray(res.dist).ravel(),
                                  np.asarray(d2))
