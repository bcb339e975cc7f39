"""The readers of the serving path's spans and counters
(``plan.device_wait_p50_ms``, ``serve.answer_host_p50_ms``,
``serve.d2h_bytes_per_answer``, ``serve.batch_idle_share``) on
hand-made requests and events, and on a trace of the toy game map
served on the CPU.

``data/cpu_serve_trace.xplane.pb`` holds, of a traced toy window
(recorded anew by ``record()``: ``PYTHONPATH=.:src JAX_PLATFORMS=cpu
python -c "from chipbench.tests.test_chipbench_serve_metrics import
record; record()"``), the spans the program and the harness name (``serve.*``,
``plan.*``, ``chipbench.*``, with their metadata) and, on a plane
``/device:CPU:0``, line ``XLA Ops``, the intervals in which the CPU
runtime ran some part of a program (the union of its threads'
``ThunkExecutor::Execute`` events, one event per busy interval), which
stand where a TPU trace has its ops. ``data/cpu_serve_trace.requests.json`` holds the window's requests
as the harness saw them: whether each failed, and its ``RequestTrace``.
"""
import dataclasses
import json
import os
import types

import pytest

from chipbench import run, trace_reduce

DATA = os.path.join(os.path.dirname(__file__), "data")
TRACE = os.path.join(DATA, "cpu_serve_trace.xplane.pb")
REQUESTS = os.path.join(DATA, "cpu_serve_trace.requests.json")
TOY_N = 40 * 56                        # the toy map's cells


def _req(failed=None, **trace):
    return types.SimpleNamespace(failed=failed, due=None,
                                 trace=types.SimpleNamespace(**trace))


def _ctx(requests, trace=None, window=None):
    return run.Context(requests, 0, 0, None, None, {}, trace, window)


def _lane(batch_id, t_solve, t_ready, t_done, d2h, failed=None):
    return _req(failed, batch_id=batch_id, t_solve=t_solve, t_ready=t_ready,
                t_done=t_done, d2h_bytes=d2h)


def test_timestamp_and_counter_readers_by_hand():
    reqs = [
        # batch 1: two lanes, dispatch returned at 1.0, ready at 3.0
        _lane(1, 1.0, 3.0, 3.5, 100), _lane(1, 1.0, 3.0, 3.5, 100),
        # batch 2: one lane; batch 3: one lane that failed after copying
        _lane(2, 4.0, 5.0, 5.25, 300), _lane(3, 6.0, 10.0, 11.0, 0, "x"),
        # a solo request: no t_ready, counts for bytes only
        _lane(4, 7.0, None, 7.5, 0),
    ]
    ctx = _ctx(reqs)
    # waits per batch 2.0, 1.0, 4.0 s: median 2.0 s
    assert run.reader("plan.device_wait_p50_ms")(ctx) == pytest.approx(2e3)
    # host answers per batch 0.5, 0.25, 1.0 s: median 0.5 s
    assert run.reader("serve.answer_host_p50_ms")(ctx) == pytest.approx(500)
    # over the four answered requests
    assert run.reader("serve.d2h_bytes_per_answer")(ctx) == 125.0


@pytest.mark.parametrize("name", ["plan.device_wait_p50_ms",
                                  "serve.answer_host_p50_ms",
                                  "serve.d2h_bytes_per_answer",
                                  "serve.batch_idle_share"])
def test_readers_read_nothing_from_a_program_without_the_counters(name):
    """A program whose ``RequestTrace`` has neither ids, ``t_ready`` nor
    ``d2h_bytes``, and whose trace has no ``serve.*`` spans."""
    reqs = [_req(t_solve=1.0, t_done=2.0, t_batch=0.5)]
    trace = trace_reduce.Trace([[("op", 0, 10)]], [[]],
                               [("chipbench.window", 0, 100)])
    assert run.reader(name)(_ctx(reqs, trace, (0, 100))) is None


def test_batch_idle_share_by_hand():
    # device busy [0, 10] and [30, 100]: one idle gap [10, 30], half of
    # it inside a serve.batch span, half inside serve.wait_work; another
    # gap [-10, 0] lies in the window before the serving spans begin
    host = [("serve.batch", 0, 20), ("serve.wait_work", 20, 40),
            ("serve.batch", 40, 100), ("serve.form_batch", 40, 41)]
    ops = [("a", 0, 10), ("b", 30, 100)]
    trace = trace_reduce.Trace([ops], [[]], host)
    share = run.reader("serve.batch_idle_share")(_ctx([], trace, (-10, 100)))
    # the window is cut to [0, 100], the serving spans' extent
    assert share == pytest.approx(100.0 * 10 / 100)
    # two chips: the second idle through the batch [40, 100] too
    trace2 = trace_reduce.Trace([ops, [("a", 0, 40)]], [[]], host)
    share2 = run.reader("serve.batch_idle_share")(_ctx([], trace2, (0, 100)))
    assert share2 == pytest.approx((10.0 + 60.0) / 2)


def _recorded():
    with open(REQUESTS) as f:
        reqs = [_req(r["failed"], **r["trace"]) for r in json.load(f)]
    tr = trace_reduce.read(TRACE, device_prefix="/device:CPU:")
    return reqs, tr


def test_recorded_trace_is_small():
    assert os.path.getsize(TRACE) < 200_000


def test_readers_on_a_recorded_cpu_trace():
    reqs, tr = _recorded()
    ctx = _ctx(reqs, tr, tr.window())
    # every answer is a point-to-point lane on the toy map, whose
    # targets are all reachable: two int32 rows each
    assert run.reader("serve.d2h_bytes_per_answer")(ctx) == 2 * TOY_N * 4
    wait = run.reader("plan.device_wait_p50_ms")(ctx)
    host = run.reader("serve.answer_host_p50_ms")(ctx)
    assert wait > 0 and host > 0
    # the host clock's stamps agree with the profiler's spans: a batch's
    # t_ready - t_solve is its serve.await_device span, within 1 ms
    batches = {}
    for r in reqs:
        if r.trace.t_ready is not None:
            batches[r.trace.batch_id] = r.trace.t_ready - r.trace.t_solve
    awaits = [e for e in tr.host if e[0] == "serve.await_device"]
    spans = [e for e in tr.host if e[0] == "serve.batch"]
    assert awaits and len(awaits) <= len(spans)
    for _, s, e in awaits:
        assert min(abs((e - s) / 1e9 - w) for w in batches.values()) < 1e-3
    # the idle inside serve.batch spans is part of the idle over the
    # serving spans' extent, the window the reader cuts
    serving = [e for e in tr.host if e[0] in ("serve.batch",
                                              "serve.wait_work")]
    cut = (min(e[1] for e in serving), max(e[2] for e in serving))
    share = run.reader("serve.batch_idle_share")(ctx)
    idle = run.reader("device.idle_share.p2p")(_ctx(reqs, tr, cut))
    assert 0.0 < share <= idle


def test_recorded_batch_spans_carry_the_request_ids():
    from jax.profiler import ProfileData

    reqs, _ = _recorded()
    by_batch = {}
    for r in reqs:
        by_batch.setdefault(r.trace.batch_id, set()).add(r.trace.request_id)
    seen = 0
    for plane in ProfileData.from_file(TRACE).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name != "serve.batch":
                    continue
                stats = dict(ev.stats)
                ids = {int(i) for i in str(stats["requests"]).split()}
                if stats["batch_id"] in by_batch:
                    assert ids == by_batch[stats["batch_id"]]
                    seen += 1
    assert seen >= 1


def record(seconds=1.5, trace_seconds=0.8):
    """Serve the toy map on the CPU, trace the window's end, and write
    the two data files (see the module's docstring)."""
    import shutil
    import tempfile

    from jax.profiler import ProfileData

    from chipbench.tests.test_chipbench_faults import P2P, toy

    tmp = tempfile.mkdtemp()
    try:
        w = run.serve_window(
            {"name": "toy.p", "chips": 1}, toy("toy-gamemap"),
            dict(P2P, rate_per_s=20.0, trace_seconds=trace_seconds),
            seconds=seconds, trace=True, start=0.0, trace_dir=tmp)
        pd = ProfileData.from_file(trace_reduce.find_xplane(w.trace_dir))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    kept = ("serve.", "plan.", "chipbench.")
    host, runs = [], []
    for plane in pd.planes:
        for line in plane.lines:
            evs = [e for e in line.events if e.name.startswith(kept)]
            if evs:
                host.append(evs)
            if line.name.startswith("tf_XLA"):
                runs += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                         for e in line.events
                         if e.name == "ThunkExecutor::Execute"]
    busy = [types.SimpleNamespace(name="ThunkExecutor::Execute", start_ns=s,
                                  duration_ns=e - s, stats=())
            for s, e in trace_reduce.merged(runs)]
    planes = [("/host:CPU", [(f"python {i}", evs)
                             for i, evs in enumerate(host)]),
              ("/device:CPU:0", [("XLA Ops", busy)])]
    with open(TRACE, "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(
            _xspace_text(planes)))
    with open(REQUESTS, "w") as f:
        json.dump([{"failed": r.failed,
                    "trace": dataclasses.asdict(r.trace)} for r in w.recs],
                  f, indent=0)


def _xspace_text(planes) -> str:
    """An XSpace text proto of ``[(plane, [(line, events)])]``."""
    out = []
    for pid, (pname, lines) in enumerate(planes, 1):
        events, stats, body = {}, {}, []
        for lid, (lname, evs) in enumerate(lines, 1):
            body.append(f"lines {{ id: {lid} name: {json.dumps(lname)} "
                        "timestamp_ns: 0")
            for e in evs:
                mid = events.setdefault(e.name, len(events) + 1)
                st = []
                for k, v in e.stats:
                    sid = stats.setdefault(k, len(stats) + 1)
                    field = ("int64_value" if isinstance(v, int) else
                             "double_value" if isinstance(v, float) else
                             "str_value")
                    val = json.dumps(str(v)) if field == "str_value" else v
                    st.append(f"stats {{ metadata_id: {sid} {field}: {val} }}")
                body.append(f"events {{ metadata_id: {mid} offset_ps: "
                            f"{int(e.start_ns) * 1000} duration_ps: "
                            f"{int(e.duration_ns) * 1000} {' '.join(st)} }}")
            body.append("}")
        meta = [f"event_metadata {{ key: {i} value {{ id: {i} name: "
                f"{json.dumps(n)} }} }}" for n, i in events.items()]
        meta += [f"stat_metadata {{ key: {i} value {{ id: {i} name: "
                 f"{json.dumps(n)} }} }}" for n, i in stats.items()]
        out.append(f"planes {{ id: {pid} name: {json.dumps(pname)}\n"
                   + "\n".join(body + meta) + "\n}")
    return "\n".join(out)

