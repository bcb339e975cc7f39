"""The serving path's spans and counters: a toy ``Server`` (a game map
served through lane batches, and a small graph with one solo
``MultiSource``) runs under ``jax.profiler`` on the CPU, and the
recorded ``.xplane.pb`` is read back with ``ProfileData``. The serving
thread's spans nest as ``repro.serve.server`` documents them, each
``serve.batch`` names the batch and requests its tickets' traces hold,
and each ``RequestTrace`` has ordered timestamps and the bytes the
server copied to the host."""
import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.api import MultiSource, PointToPoint, SingleSource
from repro.core import DeltaConfig
from repro.graphs import grid_map, watts_strogatz
from repro.graphs.structures import INF32
from repro.serve import Server

MAP_CFG = DeltaConfig(delta=13, strategy="pallas", interpret=True,
                      pred_mode="argmin")
CFG = DeltaConfig(delta=10, pred_mode="argmin")
TOP = ("serve.wait_work", "serve.batch")
NAMED = ("serve.", "plan.")


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """Serve the toy traffic on the serving thread under the profiler:
    one map lane batch (three point-to-point lanes and one single-
    source lane), the graph's solo MultiSource, one more map lane
    batch. Returns the tickets, their queries, the server and the
    recorded events."""
    g_map, free = grid_map(8, 8, seed=0)
    g = watts_strogatz(60, 4, 0.1, seed=1)
    srv = Server(lane_width=4)
    srv.admit("map", g_map, config=MAP_CFG, free_mask=free)
    srv.admit("g", g, config=CFG)
    cells = np.flatnonzero(free.ravel())
    a, b, c, d = (int(v) for v in cells[[1, -1, 5, -5]])
    traffic = [("map", PointToPoint(a, b)), ("map", PointToPoint(b, a)),
               ("map", PointToPoint(c, d)), ("map", SingleSource(a)),
               ("g", MultiSource(np.array([0, 7], np.int32))),
               ("map", PointToPoint(d, c)), ("map", SingleSource(c))]
    log_dir = str(tmp_path_factory.mktemp("serve_trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(log_dir, profiler_options=opts):
        tickets = [srv.submit(q, graph=name) for name, q in traffic]
        srv.start()
        results = [t.result(timeout=600) for t in tickets]
        srv.close()
    path = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                     recursive=True)[-1]
    lines = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                    dict(e.stats)) for e in line.events
                   if e.name.startswith(NAMED)]
            if evs:
                lines.append(sorted(evs, key=lambda e: (e[1], -e[2])))
    return dict(tickets=tickets, queries=[q for _, q in traffic],
                results=results, srv=srv, lines=lines,
                n={"map": g_map.n_nodes, "g": g.n_nodes})


def _serving_line(served):
    (line,) = [ln for ln in served["lines"]
               if any(e[0] == "serve.batch" for e in ln)]
    return line


def _children(parent, events):
    """Events directly nested in ``parent`` (not in one of its
    children), in order."""
    inner = [e for e in events if e is not parent
             and parent[1] <= e[1] and e[2] <= parent[2]]
    return [e for e in inner
            if not any(o is not e and o[1] <= e[1] and e[2] <= o[2]
                       for o in inner)]


def test_serving_thread_lies_in_top_level_spans(served):
    line = _serving_line(served)
    top = [e for e in line if e[0] in TOP]
    # the top-level spans do not overlap, and every other span of the
    # thread lies inside a serve.batch
    for prev, nxt in zip(top, top[1:]):
        assert prev[2] <= nxt[1]
    for e in line:
        if e[0] not in TOP:
            assert any(b[0] == "serve.batch" and b[1] <= e[1]
                       and e[2] <= b[2] for b in top), e[0]
    assert [e[0] for e in top].count("serve.batch") == 3


def test_batch_children_nest_in_order(served):
    line = _serving_line(served)
    kinds = []
    for batch in (e for e in line if e[0] == "serve.batch"):
        kids = [e[0] for e in _children(batch, line)]
        kind = batch[3]["kind"]
        kinds.append(kind)
        want = ["serve.form_batch", "serve.plan_build", "serve.dispatch"]
        if kind == "lanes":
            want.append("serve.await_device")
        want.append("serve.answer")
        if "serve.plan_build" not in kids:       # the plan was resident
            want.remove("serve.plan_build")
        assert kids == want, (kind, kids)
        (dispatch,) = [e for e in _children(batch, line)
                       if e[0] == "serve.dispatch"]
        assert [e[0] for e in _children(dispatch, line)] == ["plan.solve"]
        (answer,) = [e for e in _children(batch, line)
                     if e[0] == "serve.answer"]
        walks = [e[0] for e in _children(answer, line)]
        p2p = sum(isinstance(served["queries"][i], PointToPoint)
                  for i in _batch_items(served, batch[3]["batch_id"]))
        assert walks == ["serve.copy_rows", "serve.extract_path"] * p2p
    assert kinds == ["lanes", "solo", "lanes"]
    # the first batch of each tenant built its plan
    built = [e for e in line if e[0] == "serve.plan_build"]
    assert sorted(e[3]["tenant"] for e in built) == ["g", "map"]


def _batch_items(served, batch_id):
    return [i for i, t in enumerate(served["tickets"])
            if t.trace.batch_id == batch_id]


def test_batch_spans_carry_the_tickets_ids(served):
    line = _serving_line(served)
    for batch in (e for e in line if e[0] == "serve.batch"):
        meta = batch[3]
        items = _batch_items(served, meta["batch_id"])
        traces = [served["tickets"][i].trace for i in items]
        assert {int(r) for r in str(meta["requests"]).split()} == {
            t.request_id for t in traces}
        assert meta["lanes"] == len(items)
        assert {meta["tenant"]} == {t.tenant for t in traces}
    ids = [t.trace.request_id for t in served["tickets"]]
    assert ids == sorted(set(ids))
    # each submit is a span on the caller's thread, with the request id
    submits = [e for ln in served["lines"] for e in ln
               if e[0] == "serve.submit"]
    assert sorted(e[3]["request_id"] for e in submits) == ids
    assert all(e not in _serving_line(served) for e in submits)


def test_timestamps_ordered_and_ready_only_on_lanes(served):
    for t, q in zip(served["tickets"], served["queries"]):
        tr = t.trace
        if isinstance(q, MultiSource):
            assert tr.t_ready is None
            assert tr.t_submit <= tr.t_batch <= tr.t_solve <= tr.t_done
        else:
            assert (tr.t_submit <= tr.t_batch <= tr.t_solve <= tr.t_ready
                    <= tr.t_done)


def test_bytes_copied_to_the_host(served):
    total = 0
    for t, q, res in zip(served["tickets"], served["queries"],
                         served["results"]):
        n = served["n"][t.trace.tenant]
        if isinstance(q, PointToPoint):
            assert res.distance < int(INF32)
            assert t.trace.d2h_bytes == 2 * n * 4
        else:
            assert t.trace.d2h_bytes == 0
        total += t.trace.d2h_bytes
    assert total == 4 * 2 * 64 * 4
    assert served["srv"].stats()["d2h_bytes"] == total
