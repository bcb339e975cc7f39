"""Differential test harness: the engine vs the ``core/ref.py`` heap-
Dijkstra oracle on adversarial random COO graphs — zero-weight edges,
self-loops, duplicate (parallel) edges and disconnected vertices — across
**every** backend × pred_mode × Δ combination, the mesh-sharded backends
included (n_shards = every local device: 1 in a plain run, 8 under the
CI ``sharded`` job's forced host platform) — and, at a fixed Δ, the same
cross product for every non-delta frontier policy (ρ-stepping /
radius-stepping, DESIGN.md §15).

Hypothesis drives the case generation when it is installed, with a
deterministic seed-sweep fallback otherwise (shared driver:
tests/_property_driver.py). All randomness flows through one integer
seed, and every case shares one (n_nodes, n_edges) shape so the module
compiles each backend × pred × Δ program exactly once (the drivers are
module-jitted, core.delta_stepping).

Predecessor checks: ``packed`` trees are validated always (zero weights
are safe, pack.py); ``argmin`` trees only on zero-weight-free cases —
post-hoc recovery assumes weights >= 1 (a zero-weight tie can close a
predecessor cycle; test_determinism.py documents argmin's divergences).
"""
from functools import partial

import numpy as np
import pytest

from _property_driver import (
    ALL_POLICIES, ALL_STRATEGIES, drive, null_ctx as _null,
    release_compiled_programs)
from repro.compat import enable_x64
from repro.core import (
    DeltaConfig,
    DeltaSteppingSolver,
    dijkstra,
    walk_pred_tree,
)
from repro.graphs.structures import COOGraph, INF32

# one integer seed is the whole case (adversarial_coo expands it)
drive_seed = partial(
    drive,
    strategy=lambda st: st.integers(min_value=0, max_value=2**31 - 1),
    fallback_draw=lambda rng: int(rng.integers(0, 2**31)))


# One fixed shape for every case: the shape is the jit cache key, the
# arrays are arguments — so each backend × pred × Δ program compiles once.
N, M = 32, 96

BACKENDS = ALL_STRATEGIES
PRED_MODES = ("none", "argmin", "packed")
DELTAS = (1, 7, 31)


def adversarial_coo(seed: int):
    """One adversarial instance from one seed: edges confined to the
    first ``k`` vertices (the rest are guaranteed-disconnected), forced
    self-loop, forced duplicate edges with differing weights, and
    zero-weight edges on odd seeds."""
    rng = np.random.default_rng(seed)
    k = int(rng.integers(2, N + 1))
    src = rng.integers(0, k, size=M).astype(np.int64)
    dst = rng.integers(0, k, size=M).astype(np.int64)
    w_lo = int(seed % 2)                       # zero weights on odd seeds
    w = rng.integers(w_lo, 21, size=M).astype(np.int64)
    src[0] = dst[0] = int(rng.integers(0, k))  # self-loop
    src[1], dst[1] = src[2], dst[2]            # duplicate edge pair,
    w[1] = int(rng.integers(w_lo, 21))         # independent weights
    g = COOGraph(src=src.astype(np.int32), dst=dst.astype(np.int32),
                 w=w.astype(np.int32), n_nodes=N)
    source = int(rng.integers(0, k))
    return g, source, w_lo


def _solve(g, source, strategy, pred_mode, delta, policy="delta"):
    release_compiled_programs()
    # rho pinned small so the ρ-batches actually split the frontier on
    # N=32 graphs (the interesting regime; rho >= N degenerates to one
    # Dijkstra-like giant step only when every vertex is pending)
    cfg = DeltaConfig(delta=delta, strategy=strategy, pred_mode=pred_mode,
                      interpret=True, policy=policy, rho=4)
    res = DeltaSteppingSolver(g, cfg).solve(source)
    assert not bool(res.overflow), (strategy, pred_mode, delta, policy)
    return res


@drive_seed(max_examples=25, fallback_examples=10)
def test_all_backend_pred_delta_combos_match_oracle(seed):
    """Exact distance equality against heap Dijkstra for the full
    backend × pred_mode × Δ cross product on one adversarial graph, and
    sentinel handling for its disconnected tail: INF32 distance, -1
    pred. Pred trees are walked end-to-end where the mode guarantees
    validity."""
    g, source, w_lo = adversarial_coo(seed)
    dref, _ = dijkstra(g, source)
    unreachable = dref >= int(INF32)
    for delta in DELTAS:
        for strategy in BACKENDS:
            for pred_mode in PRED_MODES:
                ctx = enable_x64() if pred_mode == "packed" else _null()
                with ctx:
                    res = _solve(g, source, strategy, pred_mode, delta)
                    dist = np.asarray(res.dist, np.int64)
                    pred = np.asarray(res.pred)
                tag = (seed, strategy, pred_mode, delta)
                np.testing.assert_array_equal(dist, dref, err_msg=str(tag))
                if pred_mode == "none":
                    continue
                assert (pred[unreachable] == -1).all(), tag
                assert pred[source] == -1, tag
                if pred_mode == "packed" or w_lo >= 1:
                    assert walk_pred_tree(g, source, dist, pred), tag


@drive_seed(max_examples=40, fallback_examples=16)
def test_backends_agree_bitwise_on_adversarial_graphs(seed):
    """All backends run the same bucket schedule, so distances *and*
    iteration counters must agree bitwise across the whole backend axis
    (single-device and mesh-sharded) — not just match the oracle."""
    g, source, _ = adversarial_coo(seed)
    delta = DELTAS[seed % len(DELTAS)]
    base = _solve(g, source, "edge", "argmin", delta)
    for strategy in BACKENDS[1:]:
        res = _solve(g, source, strategy, "argmin", delta)
        np.testing.assert_array_equal(
            np.asarray(res.dist), np.asarray(base.dist), err_msg=strategy)
        np.testing.assert_array_equal(
            np.asarray(res.pred), np.asarray(base.pred), err_msg=strategy)
        assert int(res.outer_iters) == int(base.outer_iters), strategy


@drive_seed(max_examples=10, fallback_examples=5)
def test_policy_family_full_cross_product_matches_oracle(seed):
    """The frontier-policy family (ρ-stepping, radius-stepping,
    DESIGN.md §15) pinned to the heap-Dijkstra oracle across the full
    backend × pred_mode cross product on the same adversarial corpus —
    dist AND pred (packed (cost, pred) words included) must be exact.
    Δ is fixed: the non-delta policies do not bucket, Δ only moves
    their light/heavy phase split."""
    g, source, w_lo = adversarial_coo(seed)
    dref, _ = dijkstra(g, source)
    unreachable = dref >= int(INF32)
    for policy in ALL_POLICIES[1:]:             # delta covered above
        for strategy in BACKENDS:
            for pred_mode in PRED_MODES:
                ctx = enable_x64() if pred_mode == "packed" else _null()
                with ctx:
                    res = _solve(g, source, strategy, pred_mode, 7,
                                 policy=policy)
                    dist = np.asarray(res.dist, np.int64)
                    pred = np.asarray(res.pred)
                tag = (seed, policy, strategy, pred_mode)
                np.testing.assert_array_equal(dist, dref, err_msg=str(tag))
                if pred_mode == "none":
                    continue
                assert (pred[unreachable] == -1).all(), tag
                assert pred[source] == -1, tag
                if pred_mode == "packed" or w_lo >= 1:
                    assert walk_pred_tree(g, source, dist, pred), tag


@drive_seed(max_examples=15, fallback_examples=6)
def test_rho_sweep_matches_oracle(seed):
    """ρ-stepping is exact for *every* batch size: ρ=1 (Dijkstra-like,
    one distance class per round), small batches, and ρ >= |V| (one
    giant pending sweep per round)."""
    g, source, _ = adversarial_coo(seed)
    dref, _ = dijkstra(g, source)
    for rho in (1, 3, N, 4 * N):
        cfg = DeltaConfig(delta=7, strategy="edge", pred_mode="none",
                          policy="rho", rho=rho)
        res = DeltaSteppingSolver(g, cfg).solve(source)
        np.testing.assert_array_equal(
            np.asarray(res.dist, np.int64), dref, err_msg=f"rho={rho}")


@drive_seed(max_examples=20, fallback_examples=8)
def test_solve_many_lanes_match_single_solves(seed):
    """Batched multi-source lanes are bitwise identical to per-source
    solves on adversarial graphs (includes disconnected sources)."""
    g, source, _ = adversarial_coo(seed)
    srcs = np.asarray([source, 0, N - 1], np.int32)  # N-1 often isolated
    for strategy in ("edge", "sharded_edge"):
        solver = DeltaSteppingSolver(
            g, DeltaConfig(delta=7, strategy=strategy, pred_mode="argmin"))
        many = solver.solve_many(srcs)
        for i, s in enumerate(srcs):
            one = solver.solve(int(s))
            np.testing.assert_array_equal(
                np.asarray(many.dist[i]), np.asarray(one.dist),
                err_msg=f"{strategy} lane {i}")
            np.testing.assert_array_equal(
                np.asarray(many.pred[i]), np.asarray(one.pred),
                err_msg=f"{strategy} lane {i}")


def test_empty_graph_every_backend():
    """M=0 edge case (separate shape): only the source is reachable."""
    z = np.zeros((0,), np.int32)
    g = COOGraph(src=z, dst=z, w=z, n_nodes=5)
    for strategy in ("edge", "ell", "fused", "sharded_edge", "sharded_ell",
                     "sharded_fused"):
        res = _solve(g, 2, strategy, "argmin", 7)
        dist = np.asarray(res.dist, np.int64)
        assert dist[2] == 0
        assert (dist[[0, 1, 3, 4]] == int(INF32)).all()
        assert (np.asarray(res.pred) == -1).all()

