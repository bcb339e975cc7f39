"""The ``edge`` backend's light/heavy split (DESIGN.md §3).

``EdgeBackend`` partitions the edge arrays once at build into a light
half (w <= Δ) and a heavy half, and each sweep touches only its phase's
half. The reference throughout is the masked full-array sweep: the
shared ``edge_sweep`` on the unsplit arrays, where the phase mask turns
the other phase's edges into no-op words. Scatter-min ignores no-op
words, so every sweep, every driver state and every answer must be
bitwise the reference's.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _property_driver import null_ctx
from repro.api import Engine, MultiSource, SingleSource
from repro.compat import enable_x64
from repro.core import DeltaConfig, EdgeBackend, dijkstra
from repro.core import pack as packing
from repro.core.backends import RelaxBackend, edge_sweep, graph_is_canonical
from repro.core.delta_stepping import (
    _finish_pred_many,
    _run_many_vmapped,
    _run_one,
)
from repro.graphs import rmat
from repro.graphs.structures import COOGraph, INF32

DELTA = 10


def _graph(name: str) -> COOGraph:
    rng = np.random.default_rng(7)
    n, m = 60, 400
    if name == "rmat":
        return rmat(128, 1200, seed=2)
    if name == "no_edges":
        src = dst = w = np.zeros(0, np.int32)
    else:
        src = rng.integers(0, n, m)
        dst = rng.integers(0, n, m)
        dst[:12] = src[:12]                       # self-loops
        lo, hi = {"mixed": (0, 2 * DELTA), "all_light": (1, DELTA),
                  "all_heavy": (DELTA + 1, 3 * DELTA)}[name]
        w = rng.integers(lo, hi + 1, m)
        if name == "mixed":
            w[20:40] = DELTA                      # on the boundary: light
            w[40:50] = 0
    return COOGraph(jnp.asarray(src, jnp.int32), jnp.asarray(dst, jnp.int32),
                    jnp.asarray(w, jnp.int32), n)


GRAPHS = ("mixed", "all_light", "all_heavy", "rmat", "no_edges")


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class _FullEdge(RelaxBackend):
    """The reference: every sweep over all edges, phase by mask."""

    src: jax.Array
    dst: jax.Array
    w: jax.Array
    delta: int = dataclasses.field(metadata=dict(static=True))
    canonical: bool = dataclasses.field(metadata=dict(static=True))

    def sweep(self, tent, mask, bucket_i, *, light: bool, packed: bool):
        tent = edge_sweep(tent, mask, self.src, self.dst, self.w,
                          delta=self.delta, light=light, packed=packed,
                          canonical=self.canonical)
        return tent, jnp.zeros((), bool)


def _full(g: COOGraph) -> _FullEdge:
    return _FullEdge(g.src, g.dst, g.w, DELTA, graph_is_canonical(g))


def _x64_if(packed):
    return enable_x64() if packed else null_ctx()


def test_split_is_stable_and_exact():
    g = _graph("mixed")
    b = EdgeBackend.build(g, DeltaConfig(delta=DELTA, strategy="edge"))
    src, dst, w = (np.asarray(a) for a in (g.src, g.dst, g.w))
    light = w <= DELTA
    for (s, d, ww), keep in (((b.src_l, b.dst_l, b.w_l), light),
                             ((b.src_h, b.dst_h, b.w_h), ~light)):
        np.testing.assert_array_equal(np.asarray(s), src[keep])
        np.testing.assert_array_equal(np.asarray(d), dst[keep])
        np.testing.assert_array_equal(np.asarray(ww), w[keep])


@pytest.mark.parametrize("word", ["dist", "packed", "packed_canonical"])
@pytest.mark.parametrize("light", [True, False], ids=["light", "heavy"])
@pytest.mark.parametrize("name", GRAPHS)
def test_sweep_matches_masked_full_sweep(name, light, word):
    """One sweep from a random state, on both the exact and the
    capacity-padded split, against the unsplit masked sweep."""
    g = _graph(name)
    n = g.n_nodes
    packed = word != "dist"
    rng = np.random.default_rng(11)
    d = rng.integers(0, 4 * DELTA, n)
    d[rng.random(n) < 0.25] = int(INF32)
    mask = jnp.asarray(rng.random(n) < 0.4)
    with _x64_if(packed):
        cfg = DeltaConfig(delta=DELTA, strategy="edge")
        split = [EdgeBackend.build(g, cfg),
                 EdgeBackend.build(g, cfg, capacity=g.n_edges)]
        if packed:
            canonical = word == "packed_canonical"
            split = [dataclasses.replace(b, canonical=canonical)
                     for b in split]
            tent = jnp.where(
                jnp.asarray(d) < INF32,
                packing.pack(jnp.asarray(d, jnp.int32),
                             jnp.asarray(rng.integers(0, n, n), jnp.int32)),
                packing.INF_PACKED)
        else:
            canonical = False
            tent = jnp.asarray(d, jnp.int32)
        want = edge_sweep(tent, mask, g.src, g.dst, g.w, delta=DELTA,
                          light=light, packed=packed, canonical=canonical)
        for b in split:
            got, over = b.sweep(tent, mask, jnp.int32(0), light=light,
                                packed=packed)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
            assert not bool(over)


@pytest.mark.parametrize("pred_mode", ["argmin", "packed"])
@pytest.mark.parametrize("name", GRAPHS)
def test_batched_driver_matches_masked_full_sweep(name, pred_mode):
    """``_run_many_vmapped`` states, counters, dist and pred equal the
    unsplit reference's bitwise, and the distances equal Dijkstra's.
    Packed mode runs the non-canonical filter on ``mixed`` (zero-weight
    edges) and the canonical one elsewhere."""
    g = _graph(name)
    packed = pred_mode == "packed"
    srcs = np.array([0, 3, 17, 59], np.int32)
    cfg = DeltaConfig(delta=DELTA, strategy="edge", pred_mode=pred_mode)
    with _x64_if(packed):
        b = EdgeBackend.build(g, cfg)
        assert b.canonical == (name != "mixed")
        s = jnp.asarray(srcs)
        got = _run_many_vmapped(b, s, n=g.n_nodes, packed=packed)
        want = _run_many_vmapped(_full(g), s, n=g.n_nodes, packed=packed)
        for x, y in zip(got, want):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        dist, pred = _finish_pred_many(got[0], g, s, cfg)
        dist_r, pred_r = _finish_pred_many(want[0], g, s, cfg)
        np.testing.assert_array_equal(np.asarray(dist), np.asarray(dist_r))
        np.testing.assert_array_equal(np.asarray(pred), np.asarray(pred_r))
    for i, src in enumerate(srcs):
        dref, _ = dijkstra(g, int(src))
        np.testing.assert_array_equal(np.asarray(dist[i], np.int64), dref)


def test_explain_reports_exact_split_at_plan_time():
    g = _graph("mixed")
    plan = Engine(g, DeltaConfig(delta=DELTA, strategy="edge")).plan()
    n_light = int(np.sum(np.asarray(g.w) <= DELTA))
    assert plan.explain()["edge_split"] == {
        "light": n_light, "heavy": g.n_edges - n_light, "capacity": None}


@pytest.mark.parametrize("strategy", ["ell", "sharded_edge"])
def test_explain_edge_split_none_off_edge(strategy):
    g = _graph("mixed")
    plan = Engine(g, DeltaConfig(delta=DELTA, strategy=strategy)).plan()
    assert plan.explain()["edge_split"] is None


def test_updates_keep_compiled_shapes():
    """Three update batches move 3, 25 and 60 light edges to heavy. Each
    updated answer equals a cold solve of the updated graph, explain
    reports the exact counts at capacity m, and the driver compiles at
    most once after the first update."""
    g = _graph("rmat")
    cfg = DeltaConfig(delta=DELTA, strategy="edge", pred_mode="argmin")
    plan = Engine(g, cfg).plan()
    plan.solve(SingleSource(0))
    rng = np.random.default_rng(5)
    compiles = []
    for k in (3, 25, 60):
        w = np.asarray(plan.graph.w)
        # k light edges turn heavy, so every batch moves the light count
        ids = rng.choice(np.flatnonzero(w <= DELTA), size=k, replace=False)
        plan.update(ids, w[ids] + DELTA)
        before = _run_one._cache_size()
        res = plan.solve(SingleSource(0))
        compiles.append(_run_one._cache_size() - before)
        n_light = int(np.sum(np.asarray(plan.graph.w) <= DELTA))
        assert plan.explain()["edge_split"] == {
            "light": n_light, "heavy": g.n_edges - n_light,
            "capacity": g.n_edges}
        cold = Engine(plan.graph, cfg).plan().solve(SingleSource(0))
        np.testing.assert_array_equal(np.asarray(res.dist),
                                      np.asarray(cold.dist))
        np.testing.assert_array_equal(np.asarray(res.pred),
                                      np.asarray(cold.pred))
    assert compiles[0] <= 1 and compiles[1:] == [0, 0], compiles


def test_multisource_on_all_light_graph():
    """An all-light graph's heavy sweeps run over zero edges, through the
    façade's batched path too."""
    g = _graph("all_light")
    plan = Engine(g, DeltaConfig(delta=DELTA, strategy="edge",
                                 pred_mode="argmin")).plan()
    assert plan.explain()["edge_split"]["heavy"] == 0
    res = plan.solve(MultiSource([0, 5]))
    for i, src in enumerate((0, 5)):
        dref, _ = dijkstra(g, src)
        np.testing.assert_array_equal(np.asarray(res.dist[i], np.int64), dref)
