#!/usr/bin/env python3
"""Chip smoke test: the served SSSP path, once, on a TPU, at the graph
sizes ``configs/sssp_archs.py`` declares for production.

    python chip_smoke.py                # one chip: phases A and B
    python chip_smoke.py --four-chips   # four chips: the sharded phase only
    python chip_smoke.py --rehearse     # CPU rehearsal at toy sizes

Phase A (XLA path): SSSP_RMAT's R-MAT graph (2M vertices, 40M edges,
Δ = 10) admitted to ``repro.serve.Server`` with ``strategy="edge"``,
``pred_mode="argmin"`` and 8 lanes; 16 ``SingleSource`` and 16
``PointToPoint`` tickets, every one waited on.

Phase B (kernel path): SSSP_GAMEMAP's 3000×3000 occupancy grid through
``strategy="pallas"`` (``grid_relax`` + ``bucket_scan``), and
``strategy="fused"`` (``frontier_relax``) on SSSP_SMALLWORLD's
Watts–Strogatz family at degree 60, cut to 1M vertices (printed as a
``reduced`` line). Each kernel phase asserts ``tpu_custom_call`` in its
lowered program and ``Plan.explain()["kernel_path"] == "compiled"``.

Answers are checked against scipy's Dijkstra on the host (after taking
the minimum over duplicate edges, which CSR construction would sum) for
two sampled sources per graph; every predecessor tree is checked edge by
edge, and every point-to-point answer against its lane's distance
vector and the weights along its path.

``--four-chips``: on the same R-MAT graph, 8 sources with
``sharded_edge`` at ``n_shards=4`` against single-device ``edge`` in the
same process, bitwise (dist and pred); ``sharded_ell`` likewise on the
small-world graph (R-MAT's hub rows make its ELL blocks far larger than
HBM — the script prints the numbers).

Each phase prints one JSON line: what ran, at what size, compile
seconds (JAX's backend-compile events, persistent-cache reads
included), the answer check, and the plan's strategy and kernel path.
The last line is ``{"ok": true, "device": {...}}`` only when every
phase passed on a TPU. Without a TPU the script runs nothing and exits
1; ``--rehearse`` runs every phase at toy sizes with the kernels in the
Pallas interpreter, prints no ``ok`` line and also exits 1. The compile
cache is ``repro.launch.compile_cache``'s.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

# (rmat n, rmat m, grid side, small-world n)
FULL = dict(rmat_n=2_000_000, rmat_m=40_000_000, grid=3000, sw_n=1_000_000)
TOY = dict(rmat_n=4096, rmat_m=81920, grid=96, sw_n=4096)
SW_PRODUCTION_N = 6_000_000       # SSSP_SMALLWORLD.n_nodes
SW_DEGREE = 60
SERVE_TIMEOUT_S = 900
HBM_BYTES = 16 * 10**9            # one v5e chip


def emit(**fields):
    print(json.dumps(fields), flush=True)


def require(ok, what):
    """A failed answer check (kept under ``python -O``, unlike assert)."""
    if not ok:
        raise AssertionError(what)


class CompileClock:
    """Seconds JAX spent in backend compiles (persistent-cache reads
    included) and cache hits, read as deltas per phase."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def mark(self):
        return self.seconds, self.hits

    def since(self, mark):
        return round(self.seconds - mark[0], 3), self.hits - mark[1]


# ---------------------------------------------------------------------------
# host reference: min-weight edge table + scipy Dijkstra
# ---------------------------------------------------------------------------

class HostGraph:
    """The graph on the host with duplicate edges reduced to their
    minimum weight: the weight lookup for path/tree checks and the CSR
    matrix for scipy's Dijkstra."""

    def __init__(self, g):
        import numpy as np

        n = g.n_nodes
        src = np.asarray(g.src).astype(np.int64)
        dst = np.asarray(g.dst).astype(np.int64)
        w = np.asarray(g.w).astype(np.int64)
        key = src * n + dst
        order = np.lexsort((w, key))          # by key, then weight
        key = key[order]
        first = np.ones(key.shape[0], bool)
        first[1:] = key[1:] != key[:-1]
        self.n = n
        self.key = key[first]
        self.w = w[order][first]

    def weight(self, u, v):
        """Min weight of edges u->v (arrays), -1 where there is none."""
        import numpy as np

        k = np.asarray(u, np.int64) * self.n + np.asarray(v, np.int64)
        i = np.clip(np.searchsorted(self.key, k), 0, self.key.shape[0] - 1)
        return np.where(self.key[i] == k, self.w[i], -1)

    def dijkstra(self, sources):
        """int64 distances, INF32 where unreachable, one row per source."""
        import numpy as np
        import scipy.sparse
        import scipy.sparse.csgraph

        from repro.graphs.structures import INF32

        m = scipy.sparse.csr_matrix(
            (self.w.astype(np.float64), (self.key // self.n,
                                         self.key % self.n)),
            shape=(self.n, self.n))
        d = scipy.sparse.csgraph.dijkstra(m, directed=True,
                                          indices=list(sources))
        return np.where(np.isinf(d), int(INF32), d).astype(np.int64)


def check_tree(hg, source, dist, pred):
    """Every reached vertex but the source hangs off a tight edge."""
    import numpy as np

    from repro.graphs.structures import INF32

    dist = np.asarray(dist, np.int64)
    pred = np.asarray(pred, np.int64)
    require(dist[source] == 0 and pred[source] == -1, "source entry")
    reached = (dist < int(INF32))
    reached[source] = False
    v = np.flatnonzero(reached)
    p = pred[v]
    require(((p >= 0) & (p < hg.n)).all(), "pred out of range")
    w = hg.weight(p, v)
    require((w >= 0).all(), "pred edge missing from the graph")
    require((dist[p] + w == dist[v]).all(), "pred edge not tight")
    require((pred[~reached & (np.arange(hg.n) != source)] == -1).all(),
            "unreached vertex with a pred")


def check_path(hg, source, target, distance, path, lane_dist):
    import numpy as np

    from repro.graphs.structures import INF32

    require(distance == int(lane_dist[target]), "p2p != its lane's dist")
    if distance >= int(INF32):
        require(path is None, "path to an unreachable target")
        return
    path = np.asarray(path, np.int64)
    require(path[0] == source and path[-1] == target, "path ends")
    w = hg.weight(path[:-1], path[1:])
    require((w >= 0).all() and int(w.sum()) == distance, "path weights")


# ---------------------------------------------------------------------------
# serving helper
# ---------------------------------------------------------------------------

def serve(name, graph, cfg, queries, *, lane_width, free_mask=None):
    """Admit one tenant to a fresh Server, submit ``queries``, wait on
    every ticket (a rejected or failed ticket raises here). Returns
    ``(results, plan, seconds)``."""
    from repro.serve import Server

    srv = Server(lane_width=lane_width)
    srv.admit(name, graph, config=cfg, free_mask=free_mask)
    srv.start()
    t0 = time.perf_counter()
    try:
        tickets = [srv.submit(q, graph=name) for q in queries]
        results = []
        for t in tickets:
            results.append(t.result(timeout=SERVE_TIMEOUT_S))
            print(f"[{name}] {len(results)}/{len(tickets)} tickets answered "
                  f"at {time.perf_counter() - t0:.1f} s", file=sys.stderr,
                  flush=True)
    finally:
        srv.close()
    return results, srv.plan(name), time.perf_counter() - t0


def kernel_in_program(plan, sources):
    from repro.api import MultiSource

    return "tpu_custom_call" in plan.lower(MultiSource(sources)).as_text()


def sample(rng, candidates, k):
    import numpy as np

    return [int(x) for x in rng.choice(np.asarray(candidates), size=k,
                                       replace=False)]


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_a(sz, clock, seed):
    import numpy as np

    from repro.api import PointToPoint, SingleSource
    from repro.core import DeltaConfig
    from repro.graphs import rmat

    t0 = time.perf_counter()
    g = rmat(sz["rmat_n"], sz["rmat_m"], seed=0)
    n = g.n_nodes
    build_s = time.perf_counter() - t0
    rng = np.random.default_rng(seed)
    srcs = sample(rng, np.arange(n), 16)
    tgts = [int(t) for t in rng.integers(0, n, size=16)]
    queries = ([SingleSource(s) for s in srcs]
               + [PointToPoint(s, t) for s, t in zip(srcs, tgts)])
    cfg = DeltaConfig(delta=10, strategy="edge", pred_mode="argmin")
    mark = clock.mark()
    results, plan, serve_s = serve("rmat", g, cfg, queries, lane_width=8)
    compile_s, hits = clock.since(mark)

    t0 = time.perf_counter()
    hg = HostGraph(g)
    ref = hg.dijkstra(srcs[:2])
    ss = results[:16]
    for i in range(2):
        require(np.array_equal(np.asarray(ss[i].dist, np.int64), ref[i]),
                f"dist != Dijkstra for source {srcs[i]}")
    for s, r in zip(srcs, ss):
        check_tree(hg, s, r.dist, r.pred)
    for s, t, r, lane in zip(srcs, tgts, results[16:], ss):
        check_path(hg, s, t, r.distance, r.path, np.asarray(lane.dist))
    ex = plan.explain()
    emit(phase="A", ran="Server(lane_width=8): 16 SingleSource + 16 "
         "PointToPoint, all 32 tickets answered",
         graph=f"rmat n={n} m={int(g.src.shape[0])} (self-loops dropped "
         f"from {sz['rmat_m']}) delta=10",
         build_s=round(build_s, 3), compile_s=compile_s, cache_hits=hits,
         serve_s=round(serve_s, 3), check_s=round(time.perf_counter() - t0, 3),
         check=f"dist == scipy Dijkstra for sources {srcs[:2]}; 16 pred "
         "trees tight; 16 p2p == lane dist and path weights",
         strategy=ex["strategy"], kernel_path=ex["kernel_path"])


def phase_b_grid(sz, clock, seed, on_tpu):
    import numpy as np

    from repro.api import SingleSource
    from repro.core import DeltaConfig
    from repro.graphs import grid_map

    t0 = time.perf_counter()
    g, free = grid_map(sz["grid"], sz["grid"], seed=0)
    build_s = time.perf_counter() - t0
    rng = np.random.default_rng(seed + 1)
    srcs = sample(rng, np.flatnonzero(free.ravel()), 2)
    cfg = DeltaConfig(delta=13, strategy="pallas", pred_mode="argmin",
                      interpret=not on_tpu)
    mark = clock.mark()
    results, plan, serve_s = serve(
        "gamemap", g, cfg, [SingleSource(s) for s in srcs], lane_width=2,
        free_mask=free)
    compile_s, hits = clock.since(mark)
    kernel = kernel_in_program(plan, srcs)

    t0 = time.perf_counter()
    hg = HostGraph(g)
    ref = hg.dijkstra(srcs)
    for s, r, d in zip(srcs, results, ref):
        require(np.array_equal(np.asarray(r.dist, np.int64), d),
                f"dist != Dijkstra for source {s}")
        check_tree(hg, s, r.dist, r.pred)
    ex = plan.explain()
    emit(phase="B.gamemap", ran="Server(lane_width=2): 2 SingleSource "
         "through grid_relax + bucket_scan",
         graph=f"grid_map {sz['grid']}x{sz['grid']} "
         f"(n={g.n_nodes}, m={int(g.src.shape[0])}) delta=13",
         build_s=round(build_s, 3), compile_s=compile_s, cache_hits=hits,
         serve_s=round(serve_s, 3), check_s=round(time.perf_counter() - t0, 3),
         tpu_custom_call=kernel,
         check=f"dist == scipy Dijkstra and pred trees tight for {srcs}",
         strategy=ex["strategy"], kernel_path=ex["kernel_path"])
    return kernel, ex["kernel_path"]


def phase_b_fused(sz, clock, seed, on_tpu):
    import numpy as np

    from repro.api import SingleSource
    from repro.core import DeltaConfig
    from repro.graphs import watts_strogatz

    emit(reduced="SSSP_SMALLWORLD n_nodes", production=SW_PRODUCTION_N,
         run=sz["sw_n"],
         why="fused lane-pads its light ELL block to 128 columns and "
         "gathers (cap, 128) rows per iteration: compiled for a v5e at 6M "
         "vertices its program needs 19.78 GB of the chip's 15.75 GB HBM; "
         "degree 60 and p=1e-2 are kept")
    t0 = time.perf_counter()
    g = watts_strogatz(sz["sw_n"], SW_DEGREE, 1e-2, seed=0)
    build_s = time.perf_counter() - t0
    rng = np.random.default_rng(seed + 2)
    srcs = sample(rng, np.arange(g.n_nodes), 2)
    cfg = DeltaConfig(delta=10, strategy="fused", pred_mode="argmin",
                      interpret=not on_tpu)
    mark = clock.mark()
    results, plan, serve_s = serve(
        "smallworld", g, cfg, [SingleSource(s) for s in srcs], lane_width=2)
    compile_s, hits = clock.since(mark)
    kernel = kernel_in_program(plan, srcs)

    t0 = time.perf_counter()
    hg = HostGraph(g)
    ref = hg.dijkstra(srcs)
    for s, r, d in zip(srcs, results, ref):
        require(np.array_equal(np.asarray(r.dist, np.int64), d),
                f"dist != Dijkstra for source {s}")
        check_tree(hg, s, r.dist, r.pred)
        require(not bool(np.asarray(r.telemetry.overflow)), "overflow")
    ex = plan.explain()
    emit(phase="B.fused", ran="Server(lane_width=2): 2 SingleSource "
         "through frontier_relax",
         graph=f"watts_strogatz n={g.n_nodes} k={SW_DEGREE} p=0.01 "
         f"(m={int(g.src.shape[0])}) delta=10",
         build_s=round(build_s, 3), compile_s=compile_s, cache_hits=hits,
         serve_s=round(serve_s, 3), check_s=round(time.perf_counter() - t0, 3),
         tpu_custom_call=kernel,
         check=f"dist == scipy Dijkstra and pred trees tight for {srcs}",
         strategy=ex["strategy"], kernel_path=ex["kernel_path"])
    return kernel, ex["kernel_path"]


def phase_four_chips(sz, clock, seed):
    """Sharded over 4 devices vs single-device ``edge``, bitwise."""
    import numpy as np

    from repro.api import Engine, MultiSource
    from repro.core import DeltaConfig
    from repro.graphs import rmat, watts_strogatz
    from repro.graphs.structures import coo_to_csr, light_heavy_split

    def compare(tag, g, strategy, srcs):
        mark = clock.mark()
        out = {}
        for strat, shards in (("edge", None), (strategy, 4)):
            cfg = DeltaConfig(delta=10, strategy=strat, pred_mode="argmin",
                              n_shards=shards)
            t0 = time.perf_counter()
            plan = Engine(g, cfg).plan()
            res = plan.solve(MultiSource(srcs))
            out[strat] = (np.asarray(res.dist), np.asarray(res.pred),
                          time.perf_counter() - t0)
        compile_s, hits = clock.since(mark)
        (d1, p1, t1), (d4, p4, t4) = out["edge"], out[strategy]
        same = np.array_equal(d1, d4) and np.array_equal(p1, p4)
        emit(phase=f"4chip.{tag}", ran=f"{strategy} n_shards=4 vs edge on "
             f"one device, MultiSource of {len(srcs)}",
             graph=f"n={g.n_nodes} m={int(g.src.shape[0])} delta=10",
             compile_s=compile_s, cache_hits=hits,
             solve_s={"edge": round(t1, 3), strategy: round(t4, 3)},
             check="dist and pred bitwise equal" if same else "MISMATCH",
             strategy=plan.explain()["strategy"],
             kernel_path=plan.explain()["kernel_path"])
        return same

    rng = np.random.default_rng(seed + 3)
    g = rmat(sz["rmat_n"], sz["rmat_m"], seed=0)
    ok = compare("rmat", g, "sharded_edge",
                 sample(rng, np.arange(g.n_nodes), 8))
    # sharded_ell pads every shard's ELL rows to the widest row: on
    # R-MAT that is a hub. Show the size instead of asserting it.
    light, heavy = light_heavy_split(coo_to_csr(g), 10)
    widths = [int(np.diff(np.asarray(c.row_ptr)).max()) for c in
              (light, heavy)]
    shard_rows = -(-g.n_nodes // 4) + 1
    block = sum(widths) * shard_rows * 8          # nbr + w, int32 each
    emit(reduced="sharded_ell graph", production="SSSP_RMAT",
         run="SSSP_SMALLWORLD family at n=%d, degree %d" % (sz["sw_n"],
                                                            SW_DEGREE),
         why=f"R-MAT light/heavy ELL widths {widths} make each shard's "
         f"ELL blocks {block} bytes; one chip has {HBM_BYTES}")
    del g
    g = watts_strogatz(sz["sw_n"], SW_DEGREE, 1e-2, seed=0)
    ok &= compare("smallworld", g, "sharded_ell",
                  sample(rng, np.arange(g.n_nodes), 8))
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip sharded phase")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at toy sizes; always exits 1")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    need = 4 if args.four_chips else 1
    if args.rehearse and args.four_chips:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={need} "
            + os.environ.get("XLA_FLAGS", ""))
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        import jax

        from repro.launch import compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the program ({e}); run from the "
              "repository root", file=sys.stderr)
        return 2

    devices = jax.devices()
    on_tpu = devices[0].platform == "tpu"
    if not on_tpu and not args.rehearse:
        print(f"chip_smoke: no TPU — JAX sees {devices[0].platform!r} "
              "devices; nothing was run", file=sys.stderr)
        return 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} devices, found {len(devices)}",
              file=sys.stderr)
        return 1
    cache_dir = compile_cache.enable()
    emit(devices=len(devices), platform=devices[0].platform,
         kind=devices[0].device_kind, compile_cache=cache_dir,
         sizes="toy rehearsal" if args.rehearse else "production")
    sz = TOY if args.rehearse else FULL
    clock = CompileClock()

    ok = True
    try:
        if args.four_chips:
            ok = phase_four_chips(sz, clock, args.seed)
        else:
            phase_a(sz, clock, args.seed)
            for phase in (phase_b_grid, phase_b_fused):
                kernel, path = phase(sz, clock, args.seed, on_tpu)
                if on_tpu:
                    ok &= kernel and path == "compiled"
    except Exception as e:  # noqa: BLE001 — report, then fail the run
        import traceback

        traceback.print_exc()
        emit(failed=f"{type(e).__name__}: {e}")
        return 1
    if not ok:
        emit(failed="a phase did not meet its check")
        return 1
    if not on_tpu:
        print("chip_smoke: rehearsal finished; not a chip run",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
