"""A whole run at toy size on the CPU, past the harness's look for a
chip: sound runs come out correct, and the control and each fault a
one-chip cell can have, planted in the timed path, come out not
correct. (The exchange between chips does not exist on one chip.)"""
import json
import os

import jax
import jax.numpy as jnp
import pytest

from chipbench import control, run

DATA = os.path.join(os.path.dirname(__file__), "data")
BENCH = {"end_to_end": [
    {"name": "setup_s", "unit": "s"},
    {"name": "solves_per_s", "unit": "solves/s", "workloads": ["toy.a"]},
    {"name": "latency_p95_ms", "unit": "ms", "workloads": ["toy.p"]}],
    "per_layer": []}
ANALYTICS = {"loop": "closed", "query": "multi_source",
             "sources_per_request": 8, "in_flight": 2, "check_lanes": 2,
             "pool": 16, "pool_seed": 1}
P2P = {"loop": "open", "arrivals": "poisson", "query": "point_to_point",
       "rate_per_s": 200.0, "max_queue": 4096, "check_requests": 8,
       "pool": 64, "pool_seed": 1}


def toy(name, **engine):
    with open(os.path.join(DATA, name + ".json")) as f:
        cfg = json.load(f)
    return dict(cfg, engine=dict(cfg["engine"], **engine))


def run_toy(cfg, mix, seed=2**33 + 5):
    jax.clear_caches()           # a planted fault must be traced anew
    try:
        cell = {"name": "toy.a" if mix is ANALYTICS else "toy.p",
                "chips": 1}
        return run.run_cell(cell, cfg, mix, BENCH, seed=seed, seconds=0.4,
                            trace=False, require_chip=False, cache_dir=None)
    finally:
        jax.clear_caches()


@pytest.mark.parametrize("mix", [ANALYTICS, P2P], ids=["analytics", "p2p"])
def test_sound_toy_rmat_is_correct(mix):
    res = run_toy(toy("toy-rmat"), mix)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 2 and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", (
        "solves_per_s" if mix is ANALYTICS else "latency_p95_ms")}


def test_sound_toy_gamemap_is_correct():
    res = run_toy(toy("toy-gamemap"), dict(P2P, rate_per_s=10.0))
    assert res["correct"], res["checks"]
    assert res["checks"]["answers_checked"]["value"] >= 2


@pytest.mark.parametrize("mix", [ANALYTICS, P2P], ids=["analytics", "p2p"])
def test_control_is_not_correct(mix):
    res = run_toy(toy("toy-rmat", pred_mode="none"), mix)
    assert not res["correct"]


def test_narrow_control_is_not_correct():
    """The reference's distances held in bfloat16, put in the program's
    place, fail the check on the toy map, whose distances run to several
    hundred, past bfloat16's exact integers. int16 holds every one of
    them exactly, so no check can tell it from the program."""
    seeds = [5, 2**33 + 1]
    out = control.readings({"name": "toy.p", "chips": 1},
                           toy("toy-gamemap"), dict(P2P, rate_per_s=10.0),
                           seconds=0.4, seeds=seeds,
                           narrow=["bfloat16", "int16"],
                           require_chip=False, cache_dir=None)
    got = {(o["control"], o["seed"]): o for o in out}
    for s in seeds:
        assert got[("program", s)]["correct"]
        assert not got[("bfloat16", s)]["correct"]
        assert got[("bfloat16", s)]["checks"]["path_faults"] > 0
        assert got[("int16", s)]["correct"]


def _sweep_unchanged(monkeypatch):
    from repro.core import backends

    monkeypatch.setattr(backends.EdgeBackend, "sweep",
                        lambda self, tent, *a, **k: (tent,
                                                     jnp.zeros((), bool)))


def _half_batch(monkeypatch):
    from repro.api import MultiSource, engine

    multi = engine.Plan._multi

    def half(self, q):
        k = max(1, len(q.sources) // 2)
        srcs = list(q.sources[:k]) * 2
        return multi(self, MultiSource(srcs[:len(q.sources)]))

    monkeypatch.setattr(engine.Plan, "_multi", half)


def _answer_altered(monkeypatch):
    from repro.api import engine

    finish = engine._finish_pred_many

    def altered(tent, coo, srcs, cfg):
        dist, pred = finish(tent, coo, srcs, cfg)
        return jnp.where(dist > 0, dist + 1, dist), pred

    monkeypatch.setattr(engine, "_finish_pred_many", altered)


@pytest.mark.parametrize("plant", [_sweep_unchanged, _half_batch,
                                   _answer_altered])
@pytest.mark.parametrize("mix", [ANALYTICS, P2P], ids=["analytics", "p2p"])
def test_planted_fault_is_not_correct(monkeypatch, plant, mix):
    plant(monkeypatch)
    res = run_toy(toy("toy-rmat"), mix)
    assert not res["correct"], res["checks"]


def test_traced_run_reports_per_layer_metrics(tmp_path):
    bench = dict(BENCH, per_layer=[
        {"name": "driver.sweeps_per_solve.analytics", "unit": "sweeps",
         "moves": "solves_per_s", "workloads": ["toy.a"]},
        {"name": "device.idle_share.analytics", "unit": "%",
         "moves": "solves_per_s", "workloads": ["toy.a"]},
        {"name": "setup.compile_s", "unit": "s", "moves": "setup_s"}])
    res = run.run_cell({"name": "toy.a", "chips": 1}, toy("toy-rmat"),
                       ANALYTICS, bench, seed=3, seconds=0.4, trace=True,
                       require_chip=False, cache_dir=None,
                       trace_dir=str(tmp_path))
    assert res["correct"]
    # no device plane in a CPU trace: the idle share reads nothing
    assert set(res["metrics"]) == {"driver.sweeps_per_solve.analytics",
                                   "setup.compile_s"}
    assert res["metrics"]["driver.sweeps_per_solve.analytics"]["value"] > 1
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert list(res)[-1] == "checks"


def test_no_chip_no_result(capsys):
    res = run.run_cell({"name": "toy.a", "chips": 1}, toy("toy-rmat"),
                       ANALYTICS, BENCH, seed=1, seconds=0.1, trace=False,
                       require_chip=True, cache_dir=None)
    assert res is None
    assert "correct" not in capsys.readouterr().out
