"""Auto-tuning subsystem (repro.tune, DESIGN.md §7): estimator
finiteness on degenerate graphs, JSON cache round-trips, successive-
halving search behaviour (deterministic injected costs), and the
guarantee that tuning never changes answers — a tuned solve is bitwise
identical to ``delta_stepping`` with the same explicit config."""
import dataclasses
import json

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    DeltaConfig,
    DeltaSteppingSolver,
    EdgeBackend,
    delta_stepping,
    dijkstra,
    make_backend,
)
from repro.graphs import random_graph, watts_strogatz
from repro.graphs.structures import COOGraph
from repro.tune import (
    TuningCache,
    TuningRecord,
    candidate_configs,
    default_strategies,
    estimate_delta,
    fingerprint,
    graph_stats,
    resolve_config,
    tune,
)


def _empty_graph(n):
    z = jnp.zeros((0,), jnp.int32)
    return COOGraph(z, z, z, n)


# ---------------------------------------------------------------------------
# estimator
# ---------------------------------------------------------------------------

DEGENERATE = {
    "single_vertex": _empty_graph(1),
    "no_edges": _empty_graph(64),
    "self_edge_free_pair": COOGraph(
        jnp.array([0], jnp.int32), jnp.array([1], jnp.int32),
        jnp.array([0], jnp.int32), 2),
    "uniform_weights": watts_strogatz(64, 4, 0.0, seed=0, w_lo=7, w_hi=7),
    "disconnected": random_graph(150, 60, seed=5),
}


@pytest.mark.parametrize("name", sorted(DEGENERATE))
def test_estimator_finite_on_degenerate_graphs(name):
    g = DEGENERATE[name]
    stats = graph_stats(g)
    delta = estimate_delta(stats)
    assert isinstance(delta, int)
    assert np.isfinite(delta)
    assert delta >= 1
    # a DeltaConfig must accept it and the solve must terminate
    res = delta_stepping(g, 0, DeltaConfig(delta=delta, pred_mode="none"))
    dref, _ = dijkstra(g, 0)
    np.testing.assert_array_equal(np.asarray(res.dist, np.int64), dref)


def test_estimator_matches_paper_smallworld_pick():
    """Calibration pin: on the paper's small-world family (U(1,20)
    weights, k=12) the c·w̄/d̄ rule lands on the paper's Δ=10."""
    g = watts_strogatz(1_000, 12, 1e-2, seed=0)
    assert estimate_delta(graph_stats(g)) == 10


def test_fingerprint_includes_mesh_width():
    """DESIGN.md §9: the mesh-sharded backends are in the tuner's search
    space, so the cache key must carry the device count — a sharded
    winner measured on an 8-device mesh must not be served to a 1-device
    host with the same graph."""
    import jax
    fp = fingerprint(graph_stats(watts_strogatz(200, 6, 0.05, seed=0)))
    assert fp.endswith(f":dev={jax.device_count()}")


def test_tuner_can_pick_sharded_winner():
    """A planted sharded_edge winner comes back with the measured mesh
    width pinned in the record, survives the JSON round trip, and turns
    into an exact engine config."""
    import jax
    g = watts_strogatz(200, 6, 0.05, seed=0)

    def fake_measure(delta, strat, cap, policy, reps):
        return 1.0e-6 if strat == "sharded_edge" else 1.0e-3

    rec = tune(g, deltas=(5, 10),
               strategies=("edge", "ell", "sharded_edge"),
               measure_fn=fake_measure)
    assert rec.strategy == "sharded_edge"
    assert rec.n_shards == jax.device_count()
    assert TuningRecord.from_json(rec.to_json()) == rec
    cfg = rec.to_config(DeltaConfig())
    assert cfg.n_shards == rec.n_shards
    res = DeltaSteppingSolver(g, cfg).solve(0)
    dref, _ = dijkstra(g, 0)
    np.testing.assert_array_equal(np.asarray(res.dist, np.int64), dref)


def test_fingerprint_distinguishes_structure():
    a = graph_stats(watts_strogatz(300, 6, 0.05, seed=0))
    b = graph_stats(watts_strogatz(300, 8, 0.05, seed=0))
    same = graph_stats(watts_strogatz(300, 6, 0.05, seed=0))
    assert fingerprint(a) != fingerprint(b)
    assert fingerprint(a) == fingerprint(same)


def test_fingerprint_sees_diameter_not_just_degrees():
    """Regression: p=1e-4 and p=1e-2 Watts-Strogatz graphs have the
    identical degree histogram but order-of-magnitude different
    diameters — and different optimal Δ (paper Fig. 1). The cache key
    must separate them or tuned records cross-contaminate."""
    long_d = graph_stats(watts_strogatz(2_000, 12, 1e-4, seed=0))
    short_d = graph_stats(watts_strogatz(2_000, 12, 1e-2, seed=0))
    assert long_d.degree_hist == short_d.degree_hist
    assert long_d.ecc0 > short_d.ecc0
    assert fingerprint(long_d) != fingerprint(short_d)


# ---------------------------------------------------------------------------
# cache
# ---------------------------------------------------------------------------

def _record(fp="v1:n=10:m=20:deg=1:w=1-5"):
    return TuningRecord(
        fingerprint=fp, delta=7, strategy="ell", frontier_cap=128,
        source="measured", us_per_solve=123.4,
        trials=((7, "ell", 128, "delta", 123.4),
                (3, "edge", -1, "rho", 456.7)))


def test_cache_round_trips_through_json(tmp_path):
    path = str(tmp_path / "tune_cache.json")
    cache = TuningCache(path)
    rec = _record()
    cache.put(rec)
    cache.save()
    # a fresh cache object reads the identical record back
    reloaded = TuningCache(path)
    assert len(reloaded) == 1
    assert reloaded.get(rec.fingerprint) == rec
    # and the file itself is valid, versioned JSON
    with open(path) as f:
        payload = json.load(f)
    assert payload["version"] == 1
    assert rec.fingerprint in payload["records"]


def test_cache_in_memory_and_stale_schema(tmp_path):
    mem = TuningCache(None)
    mem.put(_record())
    mem.save()                      # no-op, must not raise
    assert _record().fingerprint in mem
    stale = tmp_path / "stale.json"
    stale.write_text('{"version": 999, "records": {"x": {}}}')
    assert len(TuningCache(str(stale))) == 0


def test_cache_survives_corrupt_file(tmp_path):
    """A truncated/garbage cache file must start fresh, not crash every
    --tune-cache run until someone deletes it by hand."""
    for garbage in ('{"version": 1, "records"', "not json at all",
                    '{"version": 1, "records": {"x": {"delta": "?"}}}'):
        path = tmp_path / "broken.json"
        path.write_text(garbage)
        cache = TuningCache(str(path))
        assert len(cache) == 0
        cache.put(_record())
        cache.save()                # and it is writable again
        assert len(TuningCache(str(path))) == 1
        path.unlink()


def test_record_json_round_trip():
    rec = _record()
    assert TuningRecord.from_json(rec.to_json()) == rec
    none_cap = dataclasses.replace(rec, frontier_cap=None, trials=())
    assert TuningRecord.from_json(none_cap.to_json()) == none_cap
    # the algorithm axis survives the trip
    rho_rec = dataclasses.replace(rec, policy="rho")
    assert TuningRecord.from_json(rho_rec.to_json()).policy == "rho"


def test_record_json_tolerates_pre_policy_records():
    """Cache files written before the algorithm axis existed carry no
    ``policy`` key and 4-element trial rows. They must deserialize as
    Δ-stepping records — the only policy they could have measured —
    not crash every cache load."""
    d = _record().to_json()
    del d["policy"]
    d["trials"] = [[7, "ell", 128, 123.4]]      # legacy row shape
    rec = TuningRecord.from_json(d)
    assert rec.policy == "delta"
    assert rec.trials == ((7, "ell", 128, "delta", 123.4),)
    assert rec.to_config(DeltaConfig()).policy == "delta"


def test_stale_fingerprint_version_triggers_fresh_tune(tmp_path):
    """A record keyed under an older fingerprint version (pre-policy
    search space) must silently miss — resolve gives a fresh answer, it
    does not crash and it does not serve the stale winner."""
    g = watts_strogatz(200, 6, 0.05, seed=0)
    fp = fingerprint(graph_stats(g))
    assert fp.startswith("v5:")
    stale_fp = "v4:" + fp.split(":", 1)[1]
    path = str(tmp_path / "c.json")
    cache = TuningCache(path)
    cache.put(TuningRecord(
        fingerprint=stale_fp, delta=999, strategy="ell",
        frontier_cap=None, source="measured"))
    cache.save()
    cfg = resolve_config(g, cache_path=path)     # no measurement
    assert cfg.delta != 999                      # stale winner not served
    assert cfg.delta == estimate_delta(graph_stats(g))

    calls = []

    def fake_measure(delta, strat, cap, policy, reps):
        calls.append(delta)
        return 1.0e-3 * delta

    rec = tune(g, deltas=(2, 5), strategies=("edge",),
               cache=TuningCache(path), measure_fn=fake_measure)
    assert calls                                 # fresh search ran
    assert rec.source == "measured"
    assert rec.fingerprint == fp


def test_tuner_can_pick_policy_winner(tmp_path):
    """Planted winner on the algorithm axis: the halving loop returns a
    non-delta policy, the record round-trips through the cache file, and
    to_config carries the policy into the engine."""
    g = watts_strogatz(200, 6, 0.05, seed=0)

    def fake_measure(delta, strat, cap, policy, reps):
        if (strat, policy) == ("ell", "rho"):
            return 1.0e-6                       # planted winner
        return 1.0e-3

    path = str(tmp_path / "c.json")
    rec = tune(g, deltas=(5, 10), strategies=("edge", "ell"),
               cache=TuningCache(path), measure_fn=fake_measure)
    assert (rec.strategy, rec.policy) == ("ell", "rho")
    assert any(t[3] == "rho" for t in rec.trials)
    reloaded = TuningCache(path).get(rec.fingerprint)
    assert reloaded.policy == "rho"
    cfg = reloaded.to_config(DeltaConfig())
    assert cfg.policy == "rho"
    res = DeltaSteppingSolver(g, cfg).solve(0)
    dref, _ = dijkstra(g, 0)
    np.testing.assert_array_equal(np.asarray(res.dist, np.int64), dref)


# ---------------------------------------------------------------------------
# search
# ---------------------------------------------------------------------------

def test_candidate_grid_shape():
    stats = graph_stats(watts_strogatz(200, 6, 0.05, seed=0))
    cands = candidate_configs(stats)
    deltas = {d for d, _, _, _ in cands}
    assert len(deltas) >= 3                      # geometric grid around est
    assert estimate_delta(stats) in deltas
    assert all(d >= 1 for d in deltas)
    # edge ignores packing; ell gets one candidate per cap fraction
    assert sum(1 for _, s, c, _ in cands
               if s == "edge" and c is not None) == 0
    assert any(s == "ell" and c is not None for _, s, c, _ in cands)
    # the algorithm axis rides along: every policy appears, and the
    # non-bucketing policies enter at a single central Δ (no Δ sweep)
    assert {p for _, _, _, p in cands} == {"delta", "rho", "radius"}
    assert len({d for d, _, _, p in cands if p == "rho"}) == 1


def test_successive_halving_picks_known_winner():
    g = watts_strogatz(200, 6, 0.05, seed=0)
    calls = []

    def fake_measure(delta, strat, cap, policy, reps):
        calls.append((delta, strat, cap, policy, reps))
        if (delta, strat, policy) == (5, "ell", "delta"):
            return 1.0e-6                       # planted winner
        return 1.0e-3 * delta

    rec = tune(g, deltas=(2, 5, 11, 23), measure_fn=fake_measure)
    assert (rec.delta, rec.strategy) == (5, "ell")
    assert rec.policy == "delta"
    assert rec.source == "measured"
    assert rec.us_per_solve == pytest.approx(1.0, rel=0.5)
    # halving: later rounds re-measure fewer candidates at higher reps
    assert max(reps for *_, reps in calls) > 1
    assert rec.trials                            # evidence trail kept


def test_tuner_rejects_overflowing_candidates():
    """A frontier cap the graph overflows must never be returned."""
    g = watts_strogatz(200, 6, 0.05, seed=0)

    def fake_measure(delta, strat, cap, policy, reps):
        if cap is not None:
            return float("inf") if cap < 200 else 2.0e-3
        return 1.0e-3 * delta

    rec = tune(g, deltas=(2, 5), measure_fn=fake_measure)
    assert rec.frontier_cap is None or rec.frontier_cap >= 200


def test_tune_cache_hit_skips_search(tmp_path):
    g = watts_strogatz(200, 6, 0.05, seed=0)
    cache = TuningCache(str(tmp_path / "c.json"))
    calls = []

    def fake_measure(delta, strat, cap, policy, reps):
        calls.append(delta)
        return 1.0e-3 * delta

    first = tune(g, deltas=(2, 5), strategies=("edge",),
                 cache=cache, measure_fn=fake_measure)
    assert first.source == "measured" and calls
    calls.clear()
    again = tune(g, deltas=(2, 5), strategies=("edge",),
                 cache=cache, measure_fn=fake_measure)
    assert again.source == "cache"
    assert (again.delta, again.strategy) == (first.delta, first.strategy)
    assert not calls                             # no re-measurement
    # the persisted file feeds resolve_config without measuring
    cfg = resolve_config(g, cache_path=str(tmp_path / "c.json"))
    assert cfg.delta == first.delta


# ---------------------------------------------------------------------------
# engine integration: auto config never changes answers
# ---------------------------------------------------------------------------

def test_auto_config_bitwise_equals_explicit():
    g = watts_strogatz(300, 6, 0.05, seed=0)
    auto = delta_stepping(g, 0, "auto")
    explicit = delta_stepping(
        g, 0, DeltaConfig(delta=estimate_delta(graph_stats(g))))
    for a, b in zip(auto, explicit):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_auto_config_exact_on_all_three_families():
    """Acceptance pin: config='auto' distances are identical to the best
    hand-picked config (i.e. to the Dijkstra oracle) on the paper's
    small-world, scale-free and game-map families."""
    from repro.graphs import grid_map, rmat
    gm, free = grid_map(25, 31, 0.15, seed=3)
    graphs = {
        "smallworld": (watts_strogatz(300, 6, 0.05, seed=0), 0),
        "rmat": (rmat(256, 2500, seed=2), 0),
        "gamemap": (gm, int(np.flatnonzero(np.asarray(free).ravel())[0])),
    }
    for name, (g, src) in graphs.items():
        res = delta_stepping(g, src, "auto")
        dref, _ = dijkstra(g, src)
        np.testing.assert_array_equal(
            np.asarray(res.dist, np.int64), dref, name)


def test_measured_tuned_solve_bitwise_equals_explicit():
    g = watts_strogatz(200, 6, 0.05, seed=0)
    rec = tune(g, deltas=(5, 10), strategies=("edge",))   # real, tiny search
    cfg = rec.to_config(DeltaConfig())
    tuned = DeltaSteppingSolver(g, cfg).solve(0)
    explicit = delta_stepping(
        g, 0, DeltaConfig(delta=rec.delta, strategy=rec.strategy,
                          frontier_cap=rec.frontier_cap, policy=rec.policy))
    for a, b in zip(tuned, explicit):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    dref, _ = dijkstra(g, 0)
    np.testing.assert_array_equal(np.asarray(tuned.dist, np.int64), dref)


def test_make_backend_accepts_auto():
    g = watts_strogatz(300, 6, 0.05, seed=0)
    backend = make_backend(g, "auto")
    assert isinstance(backend, EdgeBackend)
    assert backend.delta == estimate_delta(graph_stats(g))
    with pytest.raises(ValueError):
        make_backend(g, "bogus")
    with pytest.raises(ValueError):
        DeltaSteppingSolver(g, "bogus")


def test_resolve_config_revalidates_cached_cap(tmp_path):
    """A cached frontier_cap from a same-fingerprint graph must be
    re-validated before the engine gets it: overflow would mean wrong
    distances, and only the tuning layer knows the cap is tuned."""
    g = watts_strogatz(200, 6, 0.05, seed=0)
    path = str(tmp_path / "c.json")
    cache = TuningCache(path)
    cache.put(TuningRecord(
        fingerprint=fingerprint(graph_stats(g)), delta=100, strategy="ell",
        frontier_cap=2, source="measured"))      # cap the graph overflows
    cache.save()
    cfg = resolve_config(g, cache_path=path)
    assert cfg.frontier_cap is None              # dropped, not served
    res = DeltaSteppingSolver(g, cfg).solve(0)
    assert not bool(res.overflow)
    dref, _ = dijkstra(g, 0)
    np.testing.assert_array_equal(np.asarray(res.dist, np.int64), dref)
    # the core auto path cannot know its sources: cap dropped outright,
    # so solve() from ANY source stays exact
    solver = DeltaSteppingSolver(g, "auto", tune_cache=path)
    assert solver.config.frontier_cap is None
    res7 = solver.solve(7)
    assert not bool(res7.overflow)
    dref7, _ = dijkstra(g, 7)
    np.testing.assert_array_equal(np.asarray(res7.dist, np.int64), dref7)


def test_heuristic_path_skips_ecc_probe():
    g = watts_strogatz(200, 6, 0.05, seed=0)
    stats = graph_stats(g, probe_ecc=False)
    assert stats.ecc0 == -1
    assert estimate_delta(stats) == estimate_delta(graph_stats(g))
    with pytest.raises(ValueError):
        fingerprint(stats)


def test_server_overflow_falls_back_to_full_width():
    """A tuned frontier_cap validated only on the tuner's probe sources
    must not produce wrong answers for other queries: the server
    re-solves overflowing batches with an uncapped config."""
    from repro.serve import SSSPQuery, SSSPServer
    g = watts_strogatz(300, 6, 0.05, seed=0)
    cfg = DeltaConfig(delta=100, strategy="ell", frontier_cap=2,
                      pred_mode="none")
    srv = SSSPServer(g, cfg, batch_size=2)
    srv.submit(SSSPQuery(qid=0, source=0))
    (done,) = srv.run_to_completion()
    dref, _ = dijkstra(g, 0)
    np.testing.assert_array_equal(done.dist, dref)


def test_server_tunes_at_graph_load(tmp_path):
    from repro.serve import SSSPQuery, SSSPServer
    g = watts_strogatz(300, 6, 0.05, seed=0)
    srv = SSSPServer(g, "auto", batch_size=2,
                     tune_cache=str(tmp_path / "srv.json"))
    assert isinstance(srv.config, DeltaConfig)
    assert srv.config.delta == estimate_delta(graph_stats(g))
    srv.submit(SSSPQuery(qid=0, source=0))
    (done,) = srv.run_to_completion()
    dref, _ = dijkstra(g, 0)
    np.testing.assert_array_equal(done.dist, dref)


def test_default_strategies_leave_kernels_out_off_tpu():
    """Off TPU the fused kernel strategies are not measured at all: the
    interpreter or the jnp twin would stand in for the kernel."""
    import jax

    strategies = default_strategies()
    assert jax.default_backend() != "tpu"
    assert not {"fused", "sharded_fused", "pallas"} & set(strategies)
    assert {"edge", "ell"} <= set(strategies)


def test_tuner_reraises_candidate_build_errors():
    """A candidate that cannot be built is a fault to see, not a slow
    candidate to drop: here ``fused`` without a kernel path off TPU."""
    g = watts_strogatz(120, 6, 0.05, seed=0)
    with pytest.raises(ValueError, match="only for a TPU"):
        tune(g, deltas=(5,), strategies=("edge", "fused"),
             policies=("delta",))
