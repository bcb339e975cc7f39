"""SSSP launcher — the paper's workload end-to-end on real arrays.

  PYTHONPATH=src python -m repro.launch.sssp --graph smallworld \\
      --nodes 100000 --degree 20 --delta 10 --sources 4 --verify

Uses the single-device engine by default; ``--sources K`` with
``--devices 0`` solves the K sources as one batched multi-source
program (``DeltaSteppingSolver.solve_many``). ``--devices N`` (with
XLA_FLAGS=--xla_force_host_platform_device_count=N) runs the
distributed shard_map engine on an (sources × N_model) mesh.
``--strategy pallas`` / ``fused`` route relaxation through the Pallas
kernels (compiled on TPU; off TPU they need ``--interpret``); on ``--graph gamemap`` that selects the
grid-stencil kernel. ``--strategy sharded_edge`` / ``sharded_ell``
selects the mesh-sharded first-class backends (DESIGN.md §9) —
relaxation partitioned over ``--shards`` devices (default: all) inside
the unified engine, composing with ``--sources`` batching and
``--tune``:

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
      PYTHONPATH=src python -m repro.launch.sssp --graph rmat \\
      --nodes 100000 --strategy sharded_edge --shards 8 --verify

The single-host path routes through the Query/Plan façade
(``repro.api``, DESIGN.md §10): one ``Engine.plan`` resolves tuning /
strategy / caps, then queries dispatch on the plan — ``--target T``
issues an early-exit ``PointToPoint`` query instead of the full solve,
and ``--p2p-mode alt|bidirectional|alt_bidirectional`` upgrades it to
goal-directed / bidirectional search over ``--landmarks K`` ALT tables
(repro.landmarks, DESIGN.md §14).

``--tune`` replaces the hand-picked ``--delta``/``--strategy`` with the
measured (Δ, backend, packing) search (repro.tune, DESIGN.md §7);
``--tune-cache PATH`` persists/reuses tuned records across runs — with
``--tune-cache`` alone, a cache hit (or the zero-measurement estimator)
picks the config without any search.
"""
from __future__ import annotations

import argparse
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--graph", default="smallworld",
                    choices=["smallworld", "rmat", "gamemap", "lattice"])
    ap.add_argument("--nodes", type=int, default=100_000)
    ap.add_argument("--degree", type=int, default=20)
    ap.add_argument("--p", type=float, default=1e-2)
    ap.add_argument("--delta", type=int, default=10)
    ap.add_argument("--strategy", default="edge",
                    choices=["edge", "ell", "pallas", "fused",
                             "sharded_edge", "sharded_ell",
                             "sharded_fused"])
    ap.add_argument("--shards", type=int, default=None,
                    help="sharded_* strategies: 1-D mesh width "
                         "(default: every local device)")
    ap.add_argument("--policy", default="delta",
                    choices=["delta", "rho", "radius"],
                    help="frontier-selection policy (DESIGN.md §15): "
                         "the paper's bucket loop, ρ-stepping or "
                         "radius-stepping over the same backend")
    ap.add_argument("--rho", type=int, default=None,
                    help="--policy rho: batch size ρ (default: "
                         "heuristic max(32, |V|/8))")
    ap.add_argument("--radius-k", type=int, default=4,
                    help="--policy radius: r(v) = k-th smallest "
                         "outgoing edge weight")
    ap.add_argument("--interpret", action="store_true",
                    help="run the Pallas kernels in the interpreter "
                         "(off TPU only)")
    ap.add_argument("--sources", type=int, default=1)
    ap.add_argument("--target", type=int, default=None,
                    help="point-to-point query: early-exit solve from "
                         "source 0 to this vertex (repro.api facade)")
    ap.add_argument("--p2p-mode", default="early_exit",
                    choices=["early_exit", "alt", "bidirectional",
                             "alt_bidirectional"],
                    help="--target search mode: goal-directed (alt*) "
                         "and/or bidirectional Δ-stepping "
                         "(repro.landmarks, DESIGN.md §14)")
    ap.add_argument("--landmarks", type=int, default=None, metavar="K",
                    help="ALT landmark count for --p2p-mode alt* "
                         "(default 4)")
    ap.add_argument("--devices", type=int, default=0,
                    help="model-parallel width (0 = single-device engine)")
    ap.add_argument("--combine", default="reduce_scatter",
                    choices=["allreduce", "reduce_scatter"])
    ap.add_argument("--local-steps", type=int, default=1)
    ap.add_argument("--tune", action="store_true",
                    help="auto-tune (Δ, backend, packing) by measured "
                         "search instead of --delta/--strategy "
                         "(single-device engine only)")
    ap.add_argument("--tune-cache", default=None, metavar="PATH",
                    help="persistent JSON tuning cache; implies auto "
                         "config (cache hit or heuristic estimator)")
    ap.add_argument("--verify", action="store_true")
    args = ap.parse_args()

    import numpy as np

    from repro.graphs import (
        grid_map, partition_edges, rmat, square_lattice, watts_strogatz)

    t0 = time.perf_counter()
    free = None
    if args.graph == "smallworld":
        k = args.degree - args.degree % 2
        g = watts_strogatz(args.nodes, k, args.p, seed=0)
    elif args.graph == "rmat":
        g = rmat(args.nodes, args.nodes * args.degree, seed=0)
    elif args.graph == "gamemap":
        side = int(np.sqrt(args.nodes))
        g, free = grid_map(side, side, 0.1, seed=0)
        args.delta = 13
    else:
        g = square_lattice(int(np.sqrt(args.nodes)), weighted=True)
    print(f"[sssp] graph {args.graph}: |V|={g.n_nodes} |E|={g.n_edges} "
          f"({time.perf_counter() - t0:.1f}s to generate)")

    sources = list(range(args.sources))
    if args.devices:
        import jax
        from repro.compat import make_mesh
        from repro.core.distributed import (
            DistDeltaConfig, build_distributed_solver)
        n_dev = len(jax.devices())
        model = args.devices
        data = max(1, n_dev // model)
        mesh = make_mesh((data, model), ("data", "model"))
        part = partition_edges(g, model)
        solve = build_distributed_solver(
            part, mesh, DistDeltaConfig(delta=args.delta,
                                        combine=args.combine,
                                        local_steps=args.local_steps))
        t0 = time.perf_counter()
        dist, outer, inner = solve(np.asarray(sources, np.int32))
        dist = np.asarray(dist)
        dt = time.perf_counter() - t0
        print(f"[sssp] distributed ({data}x{model}, {args.combine}): "
              f"{dt * 1e3:.1f} ms, buckets={int(outer)}, "
              f"light sweeps={int(inner)}")
    else:
        # single-host path: the Query/Plan façade (DESIGN.md §10) —
        # resolution (tuning, caps) happens once in Engine.plan, solves
        # dispatch through the query algebra
        from repro.api import Engine, MultiSource, SingleSource, Tuning
        from repro.core import DeltaConfig
        cfg = DeltaConfig(delta=args.delta, strategy=args.strategy,
                          pred_mode="argmin", interpret=args.interpret,
                          n_shards=args.shards, policy=args.policy,
                          rho=args.rho, radius_k=args.radius_k)
        t0 = time.perf_counter()
        tuning = (Tuning(measure=args.tune, cache=args.tune_cache)
                  if (args.tune or args.tune_cache) else None)
        # sources= the ones actually being solved: a tuning-chosen
        # frontier cap is validated against exactly these
        plan = Engine(g, cfg, free_mask=free,
                      tuning=tuning).plan(sources=sources)
        cfg = plan.config
        if args.tune or args.tune_cache:
            print(f"[sssp] tuned config: Δ={cfg.delta} "
                  f"strategy={cfg.strategy} policy={cfg.policy} "
                  f"cap={cfg.frontier_cap} "
                  f"({time.perf_counter() - t0:.1f}s to tune)")
        elif cfg.policy != "delta":
            print(f"[sssp] frontier policy: {cfg.policy}")
        if cfg.strategy.startswith("sharded"):
            from repro.core import resolve_n_shards
            print(f"[sssp] mesh-sharded relaxation over "
                  f"{resolve_n_shards(cfg.n_shards)} device(s)")
        if args.target is not None:
            from repro.api import PointToPoint
            mode = args.p2p_mode
            if mode != "early_exit":
                t0 = time.perf_counter()
                plan.prepare_landmarks(k=args.landmarks or 4)
                print(f"[sssp] landmarks: k={plan.landmark_tables.k} "
                      f"({time.perf_counter() - t0:.1f}s to preprocess)")
            q = PointToPoint(sources[0], args.target, mode=mode)
            plan.solve(q)                       # warm up / compile
            t0 = time.perf_counter()
            r = plan.solve(q)
            dt = time.perf_counter() - t0
            hops = 0 if r.path is None else len(r.path) - 1
            print(f"[sssp] p2p {sources[0]}->{args.target}: "
                  f"dist={r.distance} hops={hops} "
                  f"buckets={int(r.telemetry.buckets)} ({mode}), "
                  f"{dt * 1e3:.1f} ms")
            if args.verify:
                from repro.core import dijkstra
                ref, _ = dijkstra(g, sources[0])
                ok = int(ref[args.target]) == r.distance
                print(f"[sssp] verify vs Dijkstra: "
                      f"{'OK' if ok else 'MISMATCH'}")
                if not ok:
                    raise SystemExit(1)
            return
        if len(sources) > 1:
            # batched multi-source path: one program for all sources
            plan.solve(MultiSource(sources))    # warm up / compile
            t0 = time.perf_counter()
            res = plan.solve(MultiSource(sources))
            dist = np.asarray(res.dist)
            dt = time.perf_counter() - t0
            tel = res.telemetry
            print(f"[sssp] Δ={cfg.delta} ({cfg.strategy}, batched x"
                  f"{len(sources)}): {dt * 1e3 / len(sources):.1f} "
                  f"ms/source, buckets={int(tel.buckets.max())}, "
                  f"light sweeps={int(tel.inner_iters.max())}")
        else:
            plan.solve(SingleSource(0))         # warm up / compile
            t0 = time.perf_counter()
            r = plan.solve(SingleSource(sources[0]))
            dist = np.asarray(r.dist)[None]
            dt = time.perf_counter() - t0
            print(f"[sssp] Δ={cfg.delta} ({cfg.strategy}): "
                  f"{dt * 1e3:.1f} ms/source, "
                  f"buckets={int(r.telemetry.buckets)}, "
                  f"light sweeps={int(r.telemetry.inner_iters)}")

    if args.verify:
        from repro.core import dijkstra
        ref, _ = dijkstra(g, sources[0])
        ok = np.array_equal(dist[0].astype(np.int64), ref)
        print(f"[sssp] verify vs Dijkstra: {'OK' if ok else 'MISMATCH'}")
        if not ok:
            raise SystemExit(1)


if __name__ == "__main__":
    from repro.launch import compile_cache

    compile_cache.enable()
    main()
