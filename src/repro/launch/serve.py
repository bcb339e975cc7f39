"""Serving launcher: batched-request demos over the serve layer.

LM decode over the slot server:

  PYTHONPATH=src python -m repro.launch.serve --arch gemma2-9b --smoke \\
      --requests 8 --max-new 16

Batched SSSP queries over the microbatching graph server (the unified
engine's multi-source program):

  PYTHONPATH=src python -m repro.launch.serve --sssp --nodes 20000 \\
      --requests 32 --batch 8
"""
from __future__ import annotations

import argparse
import time


def _serve_sssp(args):
    import numpy as np

    from repro.api import PointToPoint, SingleSource, Tuning
    from repro.core import DeltaConfig
    from repro.graphs import watts_strogatz
    from repro.serve import Server

    g = watts_strogatz(args.nodes, args.degree, 1e-2, seed=0)
    t0 = time.perf_counter()
    # --tune = measured search; --tune-cache alone = cache hit or the
    # zero-measurement estimator (same semantics as launch.sssp). The
    # concrete config is always the tuning *base*, so --strategy /
    # --shards survive tuning as non-searched fields, and the winning
    # TuningRecord attaches to the tenant's plan.
    auto = args.tune or args.tune_cache is not None
    config = DeltaConfig(delta=args.delta, strategy=args.strategy,
                         n_shards=args.shards, policy=args.policy,
                         rho=args.rho, interpret=args.interpret)
    if not auto and args.policy != "delta":
        print(f"[serve] frontier policy: {args.policy}")
    if not auto and args.strategy.startswith("sharded"):
        from repro.core import resolve_n_shards
        print(f"[serve] mesh-sharded relaxation over "
              f"{resolve_n_shards(args.shards)} device(s)")
    tuning = Tuning(measure=args.tune, cache=args.tune_cache) if auto \
        else None
    srv = Server(g, config=config, tuning=tuning, lane_width=args.batch)
    if auto:
        cfg = srv.plan().config
        rec = srv.plan().record
        provenance = "none" if rec is None else rec.source
        print(f"[serve] tuned at graph load: Δ={cfg.delta} "
              f"strategy={cfg.strategy} policy={cfg.policy} "
              f"cap={cfg.frontier_cap} "
              f"record={provenance} "
              f"({time.perf_counter() - t0:.1f}s)")
    srv.submit(SingleSource(0))
    srv.drain()                                 # warm up / compile
    rng = np.random.default_rng(0)
    t0 = time.perf_counter()
    tickets = []
    with srv:                                   # continuous batch loop
        for i in range(args.requests):
            # mix of full distance-vector and point-to-point queries
            src = int(rng.integers(g.n_nodes))
            q = (PointToPoint(src, int(rng.integers(g.n_nodes)))
                 if i % 2 else SingleSource(src))
            tickets.append(srv.submit(q))
        results = [t.result() for t in tickets]
    dt = time.perf_counter() - t0
    n_paths = sum(1 for r in results if getattr(r, "path", None) is not None)
    stats = srv.stats()
    print(f"[serve] answered {len(results)} SSSP queries ({n_paths} with "
          f"paths) in {dt:.2f}s "
          f"({len(results) / dt:.1f} qps, lanes={args.batch}, "
          f"|V|={g.n_nodes})")
    print(f"[serve] p50={stats['latency_p50_ms']:.1f} ms "
          f"p99={stats['latency_p99_ms']:.1f} ms "
          f"occupancy={stats['mean_occupancy']:.2f} "
          f"batches={stats['batches']} shed={stats['shed'] or 0}")


def _serve_lm(args):
    import jax
    import numpy as np

    from repro.configs import family_of, get_config
    from repro.models.transformer import init_lm
    from repro.serve import BatchServer, Request

    assert family_of(args.arch) == "lm", "LM serving needs an lm arch"
    cfg = get_config(args.arch, smoke=args.smoke)
    params = init_lm(cfg, jax.random.key(0))
    srv = BatchServer(params, cfg, n_slots=args.slots, max_len=args.max_len,
                      eos_id=-1)
    rng = np.random.default_rng(0)
    t0 = time.perf_counter()
    for i in range(args.requests):
        srv.submit(Request(
            rid=i,
            prompt=rng.integers(0, cfg.vocab, args.prompt_len,
                                dtype=np.int32),
            max_new=args.max_new))
    done = srv.run_to_completion()
    dt = time.perf_counter() - t0
    tokens = sum(len(r.generated) for r in done)
    print(f"[serve] completed {len(done)} requests, {tokens} tokens in "
          f"{dt:.2f}s ({tokens / dt:.1f} tok/s with {args.slots} slots)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=64)
    # SSSP serving mode
    ap.add_argument("--sssp", action="store_true",
                    help="serve batched SSSP queries instead of LM decode")
    ap.add_argument("--nodes", type=int, default=20_000)
    ap.add_argument("--degree", type=int, default=12)
    ap.add_argument("--delta", type=int, default=10)
    ap.add_argument("--strategy", default="edge",
                    choices=["edge", "ell", "fused", "sharded_edge",
                             "sharded_ell", "sharded_fused"],
                    help="SSSP mode: relaxation backend (sharded_* = "
                         "mesh-sharded engine, DESIGN.md §9)")
    ap.add_argument("--shards", type=int, default=None,
                    help="SSSP mode, sharded_* strategies: mesh width "
                         "(default: every local device)")
    ap.add_argument("--interpret", action="store_true",
                    help="SSSP mode, fused strategies off TPU: run the "
                         "kernel in the Pallas interpreter")
    ap.add_argument("--policy", default="delta",
                    choices=["delta", "rho", "radius"],
                    help="SSSP mode: frontier-selection policy "
                         "(Δ-stepping / ρ-stepping / radius-stepping, "
                         "DESIGN.md §15)")
    ap.add_argument("--rho", type=int, default=None,
                    help="SSSP mode, --policy rho: batch size ρ")
    ap.add_argument("--batch", type=int, default=8,
                    help="SSSP microbatch size (solve_many lanes)")
    ap.add_argument("--tune", action="store_true",
                    help="SSSP mode: measured auto-tune at graph load")
    ap.add_argument("--tune-cache", default=None, metavar="PATH",
                    help="SSSP mode: persistent tuning cache")
    args = ap.parse_args()

    if args.sssp:
        _serve_sssp(args)
    else:
        if not args.arch:
            ap.error("--arch is required for LM serving")
        _serve_lm(args)


if __name__ == "__main__":
    from repro.launch import compile_cache

    compile_cache.enable()
    main()
