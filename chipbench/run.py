#!/usr/bin/env python3
"""The chip benchmark: one cell of ``BENCHMARK.json``, one run.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

A cell names a configuration (``configs/<name>.json``: the graph family
and its sizes, the ``engine`` settings handed to the program's
``DeltaConfig`` as they stand, the ``server`` settings) and a traffic
mix (``traffic/<name>.json``, read by ``generator.py``). The run makes
the graph on the chip (``families/<family>.py``), admits it to
``repro.serve.Server``, warms up the cell's one lane or batch shape,
then drives the served path ``Server.submit -> Ticket.result`` for
``--seconds`` and waits for every answer due in the window. The answers
are checked against scipy's Dijkstra (``reference.py``, through the
query kind's ``check`` in ``queries/<query>.py``) after the window.

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer ones (``metrics/<name>.py``), read from host spans, the
program's counters and a profiler trace of the last ``trace_seconds``
of the window. The last line of standard output is one JSON object;
the numbers compared with the reference come last there, under
``checks``, and as the last lines of standard error.

Without a TPU, or with fewer chips than the cell asks for, the run
exits 1 and prints no result.
"""
from __future__ import annotations

import time

PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import queue  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from typing import Optional  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE_DIR = os.path.join(HERE, ".jax_cache")
TRACE_DIR = os.path.join(HERE, ".trace")
GRACE_S = 60.0          # how long past the window an answer is waited for
CLOSED_LOOP_REQUESTS = 4096


@dataclasses.dataclass
class Rec:
    """One request as the client saw it (host ``time.monotonic``)."""

    index: int
    sources: tuple
    target: int
    due: Optional[float] = None         # open loop: when it was due
    submitted: Optional[float] = None
    answered: Optional[float] = None
    failed: Optional[str] = None
    ticket: object = None
    trace: object = None                # the server's RequestTrace
    result: object = None               # kept for the check
    lanes: list = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Context:
    """What a per-layer metric reader gets. ``peaks`` is ``None`` off
    the chip, where no roofline share is read."""

    requests: list
    n: int
    m: int
    grid: Optional[tuple]
    peaks: Optional[dict]
    setup: dict
    trace: object = None
    trace_window: Optional[tuple] = None


@dataclasses.dataclass
class Window:
    """What one served window left: the deployment, the requests and
    the host clock's marks."""

    dep: object
    recs: list
    t0: float
    t_end: float
    gave_up: float
    setup_s: float
    graph_build_s: float
    compile_s: float
    cache_hits: int
    window_compiles: int
    trace_dir: Optional[str]


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_cell(root: str, workload: str):
    """``(cell, configuration, traffic mix, benchmark)`` by name."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = load_json(os.path.join(root, entry["file"]))
    mix = load_json(os.path.join(root, "chipbench", "traffic",
                                 cell["traffic"] + ".json"))
    return cell, cfg, mix, bench


def metric_names(bench: dict, workload: str, trace: bool):
    """The cell's metrics: end-to-end ones without a trace, per-layer
    ones with it; a metric without ``workloads`` belongs to every cell
    that reports the end-to-end metric it moves."""
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    if not trace:
        return [(m["name"], m["unit"]) for m in e2e]
    mine = {m["name"] for m in e2e}
    return [(m["name"], m["unit"]) for m in bench["per_layer"]
            if workload in m.get("workloads", [workload] if m["moves"] in mine
                                 else [])]


def reader(name: str, root: str = ROOT):
    """The ``read(ctx)`` function of ``chipbench/metrics/<name>.py``, or,
    where there is no such file, of the name less its last dotted part
    (``device.idle_share.p2p`` is read by ``device.idle_share.py``), so
    that one reader serves a quantity split by the metric it moves."""
    from chipbench import plugins

    parts = name.split(".")
    for k in range(len(parts), 0, -1):
        stem = ".".join(parts[:k])
        if os.path.isfile(plugins.path("metrics", stem, root)):
            return plugins.load("metrics", stem, root).read
    raise ValueError(f"no reader for metric {name!r} under "
                     f"{plugins.path('metrics', name, root)}")


def delta_config(cfg: dict):
    """The program's ``DeltaConfig`` from the configuration's ``engine``
    block as it stands (JSON lists become tuples)."""
    from repro.core import DeltaConfig

    return DeltaConfig(**{k: tuple(v) if isinstance(v, list) else v
                          for k, v in cfg["engine"].items()})


def server_kwargs(cfg: dict, mix: dict) -> dict:
    """``Server`` settings: the configuration's ``server`` block, with
    the mix's ``max_queue`` where it sets one."""
    kw = dict(cfg.get("server", {}))
    if "max_queue" in mix:
        kw["max_queue"] = int(mix["max_queue"])
    return kw


def wait_answer(rec: Rec, kind, deadline: float) -> None:
    """Block until ``rec``'s answer is on hand, as its query kind says."""
    try:
        res = rec.ticket.result(timeout=max(0.0, deadline - time.monotonic()))
        kind.ready(res)
        rec.answered = time.monotonic()
        rec.result = res
    except TimeoutError:
        rec.failed = "no answer"
    except Exception as e:  # noqa: BLE001 — a rejected or failed request
        rec.failed = f"{type(e).__name__}: {e}"
    rec.trace = rec.ticket.trace


def closed_loop(srv, kind, mix, reqs, t_end, span):
    """Keep ``in_flight`` requests outstanding until the window closes;
    then wait for those still out."""
    out, sent, pending = [], iter(reqs), []

    def send():
        r = next(sent)
        rec = Rec(r.index, r.sources, r.target)
        with span("chipbench.submit"):
            rec.submitted = time.monotonic()
            rec.ticket = srv.submit(kind.program_query(rec.sources,
                                                       rec.target), graph="g")
        pending.append(rec)

    for _ in range(int(mix["in_flight"])):
        send()
    while pending:
        rec = pending.pop(0)
        with span("chipbench.wait"):
            wait_answer(rec, kind, t_end + GRACE_S)
        out.append(rec)
        if time.monotonic() < t_end and rec.failed is None:
            send()
    return out


def open_loop(srv, kind, reqs, t0, t_end, span, on_tick=None,
              on_close=None):
    """Send each request when it is due; a waiter thread takes the
    answers in order (one tenant's answers come back in order).
    ``on_tick`` runs after each send, ``on_close`` when the window
    closes, before the answers still out are waited for."""
    recs, todo = [], queue.Queue()

    def waiter():
        while True:
            rec = todo.get()
            if rec is None:
                return
            wait_answer(rec, kind, t_end + GRACE_S)

    th = threading.Thread(target=waiter, name="chipbench-waiter")
    th.start()
    try:
        for r in reqs:
            due = t0 + r.due
            now = time.monotonic()
            if due > now:
                with span("chipbench.wait_arrival"):
                    time.sleep(due - now)
            rec = Rec(r.index, r.sources, r.target, due=due)
            with span("chipbench.submit"):
                rec.submitted = time.monotonic()
                rec.ticket = srv.submit(kind.program_query(rec.sources,
                                                           rec.target),
                                        graph="g")
            recs.append(rec)
            todo.put(rec)
            if on_tick is not None:
                on_tick()
        rest = t_end - time.monotonic()
        if rest > 0:
            with span("chipbench.wait_arrival"):
                time.sleep(rest)
        if on_close is not None:
            on_close()
    finally:
        todo.put(None)
        th.join()
    return recs


def lanes_of(rec: Rec):
    """``(buckets, inner iterations)`` of each real lane of an answer."""
    import numpy as np

    if rec.result is None:
        return []
    tel = rec.result.telemetry
    b = np.atleast_1d(np.asarray(tel.buckets)).astype(int)
    i = np.atleast_1d(np.asarray(tel.inner_iters)).astype(int)
    return list(zip(b.tolist(), i.tolist()))


def host_graph(dep):
    """The reference's copy of the graph, from the benchmark's arrays."""
    import jax

    from chipbench import reference

    return reference.HostGraph(*jax.device_get((dep.src, dep.dst, dep.w)),
                               dep.n)


def check(hg, mix, recs, seed, answered=None):
    """The numbers compared with the reference, each with its limit.
    ``answered`` stands in for the answers of ``recs`` (the control)."""
    import numpy as np

    from chipbench import generator

    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 4]))
    missing = sum(r.failed is not None for r in recs)
    if answered is None:
        answered = [r for r in recs if r.failed is None]
    checks = {"unanswered": (missing if answered else max(missing, 1), 0)}
    checks.update(generator.query_kind(mix).check(hg, mix, answered, rng))
    return checks


def open_device(cell: dict, require_chip: bool, cache_dir: Optional[str]):
    """``(device, devices)``, or ``None`` when they are not what the
    cell needs. ``cache_dir`` is JAX's persistent compilation cache
    (every program, however quick to compile), ``None`` to leave JAX's
    settings alone."""
    import jax

    if cache_dir is not None:
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    dev = devices[0]
    print(json.dumps({"platform": dev.platform, "device_kind":
                      dev.device_kind, "device_count": len(devices)}),
          file=sys.stderr, flush=True)
    if require_chip and (dev.platform != "tpu"
                         or len(devices) < int(cell["chips"])):
        print(f"run.py: the cell needs {cell['chips']} TPU chip(s); JAX "
              f"sees {len(devices)} {dev.platform!r} device(s). Nothing "
              "was run.", file=sys.stderr)
        return None
    return dev, devices


def serve_window(cell: dict, cfg: dict, mix: dict, *, seconds: float,
                 trace: bool, start: float,
                 trace_dir: str = TRACE_DIR) -> Window:
    """Build, admit and warm up; serve the mix for ``seconds``; wait for
    every answer due. A traced run traces the window's last
    ``trace_seconds`` into ``trace_dir/<cell>``."""
    import jax

    from chipbench import generator, graphs
    from chipbench.setup_clock import CompileClock

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.graphs.structures import COOGraph
    from repro.serve import Server

    kind = generator.query_kind(mix)
    clock = CompileClock()
    mark0 = clock.mark()
    t = time.monotonic()
    dep = graphs.build(cfg)
    jax.block_until_ready((dep.src, dep.dst, dep.w))
    graph_build_s = time.monotonic() - t

    srv = Server(**server_kwargs(cfg, mix))
    srv.admit("g", COOGraph(dep.src, dep.dst, dep.w, dep.n),
              config=delta_config(cfg), free_mask=dep.free)
    # warm-up: the cell's one program shape, from a vertex with no
    # out-edges (one bucket)
    warm_recs = [Rec(-1, s, tg) for s, tg in
                 kind.warm(mix, dep.warm_source, srv.lane_width)]
    for r in warm_recs:
        r.ticket = srv.submit(kind.program_query(r.sources, r.target),
                              graph="g")
    srv.start()
    try:
        for r in warm_recs:
            wait_answer(r, kind, time.monotonic() + 1200)
            if r.failed is not None:
                raise RuntimeError(f"warm-up failed: {r.failed}")
        compile_s, cache_hits, _ = clock.since(mark0)
        reqs = generator.requests(mix, dep.sources, dep.targets, seconds,
                                  CLOSED_LOOP_REQUESTS)
        trace_s = min(float(mix.get("trace_seconds") or seconds), seconds)
        span = (jax.profiler.TraceAnnotation if trace
                else contextlib.nullcontext)
        tdir = os.path.join(trace_dir, cell["name"]) if trace else None
        if trace:
            shutil.rmtree(tdir, ignore_errors=True)
        tracer = _Tracer(tdir) if trace else None
        compiles_mark = clock.mark()
        t0 = time.monotonic()
        t_end = t0 + seconds

        def tick():
            # the trace covers the window's last ``trace_s`` seconds, so
            # stopping it (seconds of work) stalls nothing measured
            if tracer is not None and time.monotonic() >= t_end - trace_s:
                tracer.start()

        tick()
        if mix["loop"] == "closed":
            recs = closed_loop(srv, kind, mix, reqs, t_end, span)
        else:
            recs = open_loop(srv, kind, reqs, t0, t_end, span, on_tick=tick,
                             on_close=tracer and tracer.stop)
        gave_up = time.monotonic()
        if tracer is not None:
            tracer.stop()
        _, _, window_compiles = clock.since(compiles_mark)
    finally:
        srv.close()
    for r in recs:
        r.lanes = lanes_of(r)
    return Window(dep, recs, t0, t_end, gave_up, t0 - start, graph_build_s,
                  compile_s, cache_hits, window_compiles, tdir)


def run_cell(cell: dict, cfg: dict, mix: dict, bench: dict, *, seed: int,
             seconds: float, trace: bool, require_chip: bool = True,
             start: float = PROCESS_START,
             cache_dir: Optional[str] = CACHE_DIR,
             trace_dir: str = TRACE_DIR) -> Optional[dict]:
    """One run of one cell. Returns the result object, or ``None`` when
    the device is not what the cell needs. A traced run writes its
    trace under ``trace_dir``."""
    opened = open_device(cell, require_chip, cache_dir)
    if opened is None:
        return None
    dev, devices = opened

    from chipbench import generator, trace_reduce
    from chipbench import stats as st
    from chipbench.peaks import peaks

    w = serve_window(cell, cfg, mix, seconds=seconds, trace=trace,
                     start=start, trace_dir=trace_dir)
    recs = w.recs
    mem_peak = int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))

    metrics = {}
    names = metric_names(bench, cell["name"], trace)
    if not trace:
        done = [r.answered for r in recs if r.failed is None]
        lat = st.latencies([r.due or r.submitted for r in recs],
                           [r.answered if r.failed is None else None
                            for r in recs], w.gave_up)
        values = {
            "setup_s": w.setup_s,
            "solves_per_s": st.solves_per_s(
                w.t0, done, generator.query_kind(mix).solves(mix), w.t_end),
            "latency_p50_ms": _ms(st.percentile(lat, 50)),
            "latency_p95_ms": _ms(st.percentile(lat, 95)),
        }
        for name, unit in names:
            if values.get(name) is not None:
                metrics[name] = {"value": values[name], "unit": unit}
    else:
        tr = trace_reduce.read(trace_reduce.find_xplane(w.trace_dir))
        lo, hi = tr.window()
        ctx = Context(recs, w.dep.n, int(w.dep.src.shape[0]), w.dep.grid,
                      peaks(dev.device_kind) if require_chip else None,
                      {"graph_build_s": w.graph_build_s,
                       "compile_s": w.compile_s},
                      tr, (lo, hi))
        for name, unit in names:
            v = reader(name)(ctx)
            if v is not None:
                metrics[name] = {"value": v, "unit": unit}

    lateness = [r.submitted - r.due for r in recs if r.due is not None]
    batches = {r.trace.t_batch: r.trace.t_done - r.trace.t_batch
               for r in recs if r.trace is not None
               and r.trace.t_batch is not None and r.trace.t_done is not None}
    print(json.dumps({
        "requests": len(recs), "answered": sum(r.failed is None
                                              for r in recs),
        "generator_late_p95_ms": _ms(st.percentile(lateness, 95)),
        "generator_late_max_ms": _ms(max(lateness)) if lateness else None,
        "batches": len(batches),
        "batch_s_p50": st.percentile(list(batches.values()), 50),
        "answered_in_window": sum(r.failed is None and r.answered <= w.t_end
                                  for r in recs),
        "compiles_in_window": w.window_compiles,
        "setup_cache_hits": w.cache_hits,
        "graph_build_s": w.graph_build_s, "compile_s": w.compile_s,
        "edges": w.dep.n_real_edges, "vertices": w.dep.n}),
        file=sys.stderr, flush=True)

    t = time.monotonic()
    checks = check(host_graph(w.dep), mix, recs, seed)
    print(json.dumps({"check_s": time.monotonic() - t}), file=sys.stderr,
          flush=True)
    result = {
        "correct": is_correct(checks),
        "attempted": len(recs),
        "failed": sum(r.failed is not None for r in recs),
        "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices), "memory_peak_bytes": mem_peak},
    }
    if trace:
        tr_dev = [trace_reduce.busy_ns(ops, lo, hi) for ops in tr.device]
        result["device"]["busy_s"] = (sum(tr_dev) / len(tr_dev) / 1e9
                                      if tr_dev else 0.0)
        result["device"]["window_s"] = (hi - lo) / 1e9
        result["breakdown"] = trace_reduce.breakdown(tr, lo, hi)
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        print(f"check {k} = {v} (limit {lim})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return result


def is_correct(checks: dict) -> bool:
    return all(lim is None or v <= lim for v, lim in checks.values())


def _ms(s):
    return None if s is None else 1e3 * s


class _Tracer:
    """The profiler over one span of the window, marked in the trace by
    a ``chipbench.window`` annotation."""

    def __init__(self, trace_dir: str):
        self.dir = trace_dir
        self.span = None
        self.done = False

    def start(self):
        import jax

        from chipbench.trace_reduce import WINDOW_SPAN

        if self.span is None and not self.done:
            jax.profiler.start_trace(self.dir)
            self.span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
            self.span.__enter__()

    def stop(self):
        import jax

        if self.span is not None and not self.done:
            self.span.__exit__(None, None, None)
            jax.profiler.stop_trace()
        self.done = True


def prepare() -> Optional[str]:
    """Put the checkout on the import path; an error message where the
    program is not in it."""
    # the TPU runtime would log under /tmp; a run writes only inside its
    # checkout and the directories it is given
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, ROOT)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        return "the program (src/repro) is not in this checkout"
    return None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    err = prepare()
    if err:
        print(f"run.py: {err}", file=sys.stderr)
        return 2
    cell, cfg, mix, bench = load_cell(ROOT, args.workload)
    res = run_cell(cell, cfg, mix, bench, seed=args.seed,
                   seconds=args.seconds, trace=bool(args.trace))
    return 1 if res is None else 0


if __name__ == "__main__":
    sys.exit(main())
