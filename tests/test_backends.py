"""Unified relaxation-backend engine: the edge / ell / pallas backends
all run through the single loop driver (core.delta_stepping._run_backend)
and must agree with the Dijkstra oracle on the paper's graph classes.
The pallas backend (interpret mode on CPU) is the path that exercises
kernels/ell_relax + kernels/bucket_scan, and kernels/grid_relax on the
game-map class."""
import numpy as np
import pytest

from repro.core import (
    DeltaConfig,
    DeltaSteppingSolver,
    EdgeBackend,
    EllBackend,
    GridPallasBackend,
    PallasEllBackend,
    dijkstra,
    make_backend,
)
from repro.graphs import grid_map, rmat, watts_strogatz


def _graphs():
    g, free = grid_map(25, 31, 0.15, seed=3)
    return {
        "smallworld": (watts_strogatz(300, 6, 0.05, seed=0), None),
        "rmat": (rmat(256, 2500, seed=2), None),
        "gamemap": (g, free),
    }


GRAPHS = _graphs()


def _free_src(free):
    return int(np.flatnonzero(np.asarray(free).ravel())[0])


@pytest.mark.parametrize("name", sorted(GRAPHS))
@pytest.mark.parametrize("strategy", ["edge", "ell", "pallas"])
def test_backend_matches_dijkstra(name, strategy):
    g, free = GRAPHS[name]
    delta = 13 if name == "gamemap" else 10
    src = 0 if name != "gamemap" else _free_src(free)
    cfg = DeltaConfig(delta=delta, strategy=strategy, interpret=True)
    solver = DeltaSteppingSolver(
        g, cfg, free_mask=free if strategy == "pallas" else None)
    res = solver.solve(src)
    dref, _ = dijkstra(g, src)
    np.testing.assert_array_equal(np.asarray(res.dist, np.int64), dref)
    assert not bool(res.overflow)


def test_backend_routing():
    g, free = GRAPHS["gamemap"]
    assert isinstance(
        make_backend(g, DeltaConfig(strategy="edge")), EdgeBackend)
    assert isinstance(
        make_backend(g, DeltaConfig(strategy="ell")), EllBackend)
    # off TPU a kernel strategy runs only on an explicit interpret choice
    kern = DeltaConfig(strategy="pallas", interpret=True)
    assert isinstance(make_backend(g, kern), PallasEllBackend)
    assert isinstance(make_backend(g, kern, free_mask=free),
                      GridPallasBackend)
    with pytest.raises(ValueError):
        make_backend(g, DeltaConfig(strategy="pallas", pred_mode="packed",
                                    interpret=True),
                     free_mask=free)


@pytest.mark.parametrize("name", sorted(GRAPHS))
def test_backends_agree_bitwise(name):
    """All backends run the same bucket schedule, so distances and
    iteration counters must agree exactly, not just in the limit."""
    g, free = GRAPHS[name]
    delta = 13 if name == "gamemap" else 10
    src = 0 if name != "gamemap" else _free_src(free)
    results = {}
    for strategy in ("edge", "ell", "pallas"):
        cfg = DeltaConfig(delta=delta, strategy=strategy, interpret=True)
        solver = DeltaSteppingSolver(
            g, cfg, free_mask=free if strategy == "pallas" else None)
        results[strategy] = solver.solve(src)
    base = results["edge"]
    for strategy, res in results.items():
        np.testing.assert_array_equal(np.asarray(res.dist),
                                      np.asarray(base.dist), strategy)
        assert int(res.outer_iters) == int(base.outer_iters), strategy


def test_pallas_ell_backend_with_frontier_cap():
    g, _ = GRAPHS["smallworld"]
    dref, _ = dijkstra(g, 0)
    res = DeltaSteppingSolver(
        g, DeltaConfig(delta=10, strategy="pallas", interpret=True,
                       frontier_cap=g.n_nodes)).solve(0)
    np.testing.assert_array_equal(np.asarray(res.dist, np.int64), dref)
    # a tiny cap must trip the overflow flag through the pallas path too
    res2 = DeltaSteppingSolver(
        g, DeltaConfig(delta=100, strategy="pallas", interpret=True,
                       frontier_cap=2)).solve(0)
    assert bool(res2.overflow)

# ---------------------------------------------------------------------------
# kernel path: chosen once at build, never substituted behind the caller
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("strategy", ["pallas", "fused", "sharded_fused"])
def test_kernel_strategy_off_tpu_needs_an_explicit_path(strategy):
    """Off TPU a kernel strategy builds only on an explicit choice —
    the Pallas interpreter, or (fused only) the jnp twin — and
    ``Plan.explain()`` names the path that runs."""
    from repro.api import Engine

    g, _ = GRAPHS["smallworld"]
    with pytest.raises(ValueError, match="only for a TPU"):
        make_backend(g, DeltaConfig(strategy=strategy))
    plan = Engine(g, DeltaConfig(strategy=strategy, interpret=True)).plan()
    assert plan.explain()["kernel_path"] == "interpret"
    if strategy == "pallas":
        with pytest.raises(ValueError, match="no jnp twin"):
            make_backend(g, DeltaConfig(strategy=strategy, kernel_twin=True))
    else:
        plan = Engine(g, DeltaConfig(strategy=strategy,
                                     kernel_twin=True)).plan()
        assert plan.explain()["kernel_path"] == "twin"


def test_kernel_path_on_tpu_is_always_compiled(monkeypatch):
    """On a TPU every kernel strategy runs compiled: asking for the
    interpreter or the twin there is an error, not a silent swap."""
    import jax

    from repro.core.backends import kernel_path

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for strategy in ("pallas", "fused", "sharded_fused"):
        assert kernel_path(DeltaConfig(strategy=strategy)) == "compiled"
        with pytest.raises(ValueError, match="off-TPU choices"):
            kernel_path(DeltaConfig(strategy=strategy, interpret=True))
    with pytest.raises(ValueError, match="off-TPU choices"):
        kernel_path(DeltaConfig(strategy="fused", kernel_twin=True))
    assert kernel_path(DeltaConfig(strategy="edge")) == "xla"


def test_non_kernel_strategies_report_xla():
    from repro.api import Engine

    g, _ = GRAPHS["rmat"]
    for strategy in ("edge", "ell", "sharded_edge", "sharded_ell"):
        plan = Engine(g, DeltaConfig(strategy=strategy)).plan()
        assert plan.explain()["kernel_path"] == "xla", strategy
    with pytest.raises(ValueError, match="choose one"):
        make_backend(g, DeltaConfig(strategy="fused", kernel_twin=True,
                                    interpret=True))
