"""The harness is driven by data: cells, configurations, traffic mixes
and per-layer metrics are found by name; traffic is fixed by the seed."""
import json
import os
import re

import numpy as np
import pytest

from chipbench import generator, graphs, plugins, run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_traffic_is_fixed_by_the_mix():
    mix = {"loop": "open", "arrivals": "poisson", "query": "point_to_point",
           "rate_per_s": 5.0, "pool": 300, "pool_seed": 3}
    src, tgt = np.arange(100, 200), np.arange(1000)
    a = generator.requests(mix, src, tgt, 51.0, 0)
    assert a == generator.requests(mix, src, tgt, 51.0, 0)
    c = generator.requests(dict(mix, pool_seed=4), src, tgt, 51.0, 0)
    assert a != c
    assert all(100 <= r.sources[0] < 200 and 0 <= r.target < 1000
               for r in a)
    assert len({(r.sources, r.target) for r in a}) > 0.6 * len(a)
    gaps = np.diff([r.due for r in a])
    assert gaps.mean() == pytest.approx(1 / 5.0, rel=0.1)
    closed = {"loop": "closed", "query": "multi_source",
              "sources_per_request": 8, "pool": 4, "pool_seed": 1}
    x = generator.requests(closed, src, tgt, 51.0, 5)
    assert x == generator.requests(closed, src, tgt, 51.0, 5)
    assert len(x) == 5 and all(len(r.sources) == 8 for r in x)
    assert x[4].sources in {r.sources for r in x[:4]}     # the pool cycles


def test_poisson_gaps_are_the_same_multiset_in_another_order():
    poisson = plugins.load("arrivals", "poisson")
    d1 = np.diff(poisson.dues({"rate_per_s": 4.0}, 100.0, 1))[:300]
    d2 = np.diff(poisson.dues({"rate_per_s": 4.0}, 100.0, 2))[:300]
    assert not np.allclose(d1, d2)
    assert d1.mean() == pytest.approx(0.25, rel=0.1)
    assert np.quantile(d1, 0.5) == pytest.approx(np.quantile(d2, 0.5),
                                                 rel=0.2)


NEW_FAMILY = """
import numpy as np
from chipbench.graphs import Deployment

def build(cfg, key):
    n = int(cfg["n_nodes"])
    src = np.arange(n)
    return Deployment(src, (src + 1) % n, np.ones(n, np.int32), n, None,
                      src, src, 0, n)
"""
NEW_QUERY = """
from chipbench.plugins import load

def draw(rng, mix, sources, targets):
    pick = load("draws", mix["source_draw"]).draw
    return (int(pick(rng, sources)),), -1
"""
NEW_DRAW = "def draw(rng, vertices, size=None):\n    return vertices[0]\n"
NEW_ARRIVALS = ("def dues(mix, seconds, seed):\n"
                "    return [float(t) for t in range(int(seconds))]\n")


def test_new_files_are_found_by_name(tmp_path, monkeypatch):
    bench = {"workloads": [{"name": "new.cell", "config": "newcfg",
                            "traffic": "newmix", "chips": 1}],
             "configs": [{"name": "newcfg",
                          "file": "chipbench/configs/newcfg.json"}],
             "end_to_end": [{"name": "setup_s", "unit": "s"}],
             "per_layer": [{"name": "new.metric", "unit": "%",
                            "moves": "setup_s"}]}
    files = {"configs/newcfg.json": json.dumps({"family": "ring",
                                                "n_nodes": 64,
                                                "graph_seed": 1}),
             "traffic/newmix.json": json.dumps({
                 "loop": "open", "arrivals": "each_second",
                 "query": "one_source", "source_draw": "first",
                 "pool": 3, "pool_seed": 1}),
             "metrics/new.metric.py": "def read(ctx):\n    return 42.0\n",
             "families/ring.py": NEW_FAMILY,
             "queries/one_source.py": NEW_QUERY,
             "draws/first.py": NEW_DRAW,
             "arrivals/each_second.py": NEW_ARRIVALS}
    for name, text in files.items():
        (tmp_path / "chipbench" / name).parent.mkdir(parents=True,
                                                     exist_ok=True)
        (tmp_path / "chipbench" / name).write_text(text)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell, cfg, mix, b = run.load_cell(str(tmp_path), "new.cell")
    assert cfg["n_nodes"] == 64 and mix["loop"] == "open"
    assert run.metric_names(b, "new.cell", trace=True) == [
        ("new.metric", "%")]
    # a metric split by the end-to-end metric it moves shares the reader
    for name in ("new.metric", "new.metric.p2p"):
        assert run.reader(name, root=str(tmp_path))(None) == 42.0
    monkeypatch.setattr(plugins, "HERE", str(tmp_path / "chipbench"))
    dep = graphs.build(cfg)
    assert dep.n == 64 and list(dep.dst[:2]) == [1, 2]
    reqs = generator.requests(mix, dep.sources, dep.targets, 5.0, 0)
    assert [r.due for r in reqs] == [0.0, 1.0, 2.0, 3.0, 4.0]
    assert all(r.sources == (0,) for r in reqs)
    with pytest.raises(ValueError, match="no families named"):
        graphs.build(dict(cfg, family="nowhere"))


def test_committed_benchmark_is_complete():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for cell in bench["workloads"]:
        _, cfg, mix, _ = run.load_cell(ROOT, cell["name"])
        parts = [("families", cfg["family"]), ("queries", mix["query"])]
        parts += [("draws", mix[k]) for k in ("source_draw", "target_draw")
                  if k in mix]
        if mix["loop"] == "open":
            parts.append(("arrivals", mix["arrivals"]))
        for kind, name in parts:
            assert os.path.isfile(plugins.path(kind, name)), (kind, name)
        assert run.delta_config(cfg).strategy == cfg["engine"]["strategy"]
        e2e = run.metric_names(bench, cell["name"], trace=False)
        assert "setup_s" in dict(e2e) and len(e2e) >= 2
        assert run.metric_names(bench, cell["name"], trace=True)
    metrics = bench["end_to_end"] + bench["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(set(names)) == len(names)
    for m in metrics:
        assert NAME.match(m["name"]) and m["better"] in ("lower", "higher")
    for m in bench["per_layer"]:
        assert callable(run.reader(m["name"]))
        assert m["moves"] in names
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
