"""Mesh-sharded backends vs their single-device twins (DESIGN.md §9).

Times one SSSP solve per backend on the small-world family: ``edge`` vs
``sharded_edge``, ``ell`` vs ``sharded_ell`` and the fused frontier
kernel pair ``fused`` vs ``sharded_fused`` (DESIGN.md §12), plus a
batched multi-source row through the sharded engine. Shard width is
every local device — 1 on plain CPU CI (which still exercises the full
shard_map + all-reduce-min machinery, so the gate tracks its overhead);
run under ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` for
multi-shard numbers (the derived column records the width either way).
The multi-shard *scaling curve* lives in bench_scaling_shards.py.

The fused rows run at the strategy's natural operating point: a
compacted-frontier capacity probed from the instance's bucket census
(max bucket population + power-of-two headroom), so the per-iteration
gather is O(cap·deg) instead of O(|V|·deg) — the measured content of
the fusion claim. The solve is checked overflow-free at that cap. Off
TPU the fused pair runs the kernel's bitwise jnp twin, chosen
explicitly (``kernel_twin=True``): these rows time XLA:CPU, not the
kernel."""
from __future__ import annotations

import jax
import numpy as np

from benchmarks.common import row, scaled, time_fn
from repro.core import DeltaConfig, DeltaSteppingSolver
from repro.graphs import watts_strogatz

_DELTA = 10


def _probed_cap(g, dist) -> int:
    """Max final bucket population, rounded up to a 128-lane multiple —
    a true upper bound on any light frontier and per-bucket settled set
    for w >= 1 graphs (while bucket i is current, a member's tent can
    only move within bucket i, so every transient member is a final
    member), hence the capped run is overflow-free. The solve below
    still asserts the flag."""
    d = np.asarray(dist, np.int64)
    finite = d[d < np.iinfo(np.int32).max]
    pop = int(np.bincount(finite // _DELTA).max()) if finite.size else 1
    return min(-(-pop // 128) * 128, g.n_nodes)


def main():
    g = watts_strogatz(scaled(10_000), 12, 1e-2, seed=0)
    shards = jax.device_count()
    tag = f"shards={shards}"
    times = {}
    for strategy in ("edge", "sharded_edge", "ell", "sharded_ell"):
        solver = DeltaSteppingSolver(
            g, DeltaConfig(delta=_DELTA, strategy=strategy,
                           pred_mode="none"))
        t = time_fn(lambda: solver.solve(0).dist, reps=3)
        times[strategy] = t
        derived = tag if strategy.startswith("sharded") else ""
        if strategy == "sharded_edge":
            derived += f";vs_edge={times['edge'] / t:.2f}"
        elif strategy == "sharded_ell":
            derived += f";vs_ell={times['ell'] / t:.2f}"
        row(f"sharded/{strategy}/solve", t, derived)
    # fused pair at the probed compacted-frontier capacity
    ref = DeltaSteppingSolver(
        g, DeltaConfig(delta=_DELTA, strategy="edge",
                       pred_mode="none")).solve(0)
    cap = _probed_cap(g, ref.dist)
    twin = jax.default_backend() != "tpu"
    for strategy in ("fused", "sharded_fused"):
        cfg = DeltaConfig(delta=_DELTA, strategy=strategy, pred_mode="none",
                          frontier_cap=cap, kernel_twin=twin)
        solver = DeltaSteppingSolver(g, cfg)
        res = solver.solve(0)
        assert not bool(res.overflow), (strategy, cap)
        t = time_fn(lambda: solver.solve(0).dist, reps=3)
        times[strategy] = t
        if strategy == "fused":
            derived = f"cap={cap};vs_ell={times['ell'] / t:.2f}"
        else:
            derived = (f"{tag};cap={cap};"
                       f"vs_fused={times['fused'] / t:.2f}")
        row(f"sharded/{strategy}/solve", t, derived)
    # batched multi-source through the sharded engine (vmapped shard_map)
    batch = 8
    srcs = np.arange(batch, dtype=np.int32)
    solver = DeltaSteppingSolver(
        g, DeltaConfig(delta=_DELTA, strategy="sharded_edge",
                       pred_mode="none"))
    t_bat = time_fn(lambda: solver.solve_many(srcs).dist, reps=2)
    row("sharded/sharded_edge/batched", t_bat / batch,
        f"{tag};batch={batch}")


if __name__ == "__main__":
    main()
