"""Multi-shard scaling curve: shards ∈ {1, 2, 4, 8} for every
mesh-sharded strategy (``sharded_edge``, ``sharded_ell``,
``sharded_fused``) on the small-world family, with parallel efficiency
E(k) = T(1) / (k·T(k)) recorded next to the raw timings.

Each shard width runs in a fresh subprocess that forces an 8-device
host platform *before* JAX initializes — so the sweep produces the
same rows on any host, including the single-device bench-gate runner
(where the "mesh" is 8 XLA host devices over the same cores: expect
E(k) ≈ 1/k there; the curve is about the trend on real meshes, which
is why the efficiency rows are ``gate=False``). Timing rows are gated
like every other bench row.

``sharded_fused`` runs at the census-probed compacted-frontier cap
(bench_sharded._probed_cap), its natural operating point, as the
kernel's bitwise jnp twin (the children are CPU-only); the child
asserts the solve is overflow-free at that cap.

This is a host-forced CPU curve by construction: the children are
pinned to ``JAX_PLATFORMS=cpu``, so none of them ever needs a chip.
On a TPU host the module refuses to run — the parent would hold the
chip while the children start — and the four-chip sharded check is
``chip_smoke.py --four-chips``, which drives every chip from one
process."""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

from benchmarks.bench_sharded import _DELTA, _probed_cap
from benchmarks.common import row, scaled
from repro.core import DeltaConfig, DeltaSteppingSolver
from repro.graphs import watts_strogatz

SHARD_COUNTS = (1, 2, 4, 8)
STRATEGIES = ("sharded_edge", "sharded_ell", "sharded_fused")

_CHILD = textwrap.dedent("""
    import os, sys, warnings
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ["JAX_PLATFORMS"] = "cpu"
    warnings.filterwarnings("ignore", category=DeprecationWarning)
    n, shards, cap = (int(a) for a in sys.argv[1:4])
    from benchmarks.common import time_fn
    from repro.core import DeltaConfig, DeltaSteppingSolver
    from repro.graphs import watts_strogatz

    g = watts_strogatz(n, 12, 1e-2, seed=0)
    for strategy in ("sharded_edge", "sharded_ell", "sharded_fused"):
        kw = {"frontier_cap": cap} if strategy == "sharded_fused" else {}
        solver = DeltaSteppingSolver(
            g, DeltaConfig(delta=%d, strategy=strategy, n_shards=shards,
                           pred_mode="none",
                           kernel_twin=strategy == "sharded_fused", **kw))
        res = solver.solve(0)
        assert not bool(res.overflow), (strategy, shards, cap)
        t = time_fn(lambda: solver.solve(0).dist, reps=3)
        print(f"RESULT,{strategy},{t:.9f}", flush=True)
""" % _DELTA)


def _run_width(n: int, shards: int, cap: int) -> dict:
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (root, os.path.join(root, "src"),
                    env.get("PYTHONPATH")) if p)
    out = subprocess.run(
        [sys.executable, "-c", _CHILD, str(n), str(shards), str(cap)],
        env=env, capture_output=True, text=True, timeout=1800)
    if out.returncode != 0:
        raise RuntimeError(
            f"shards={shards} child failed:\n{out.stderr[-4000:]}")
    times = {}
    for line in out.stdout.splitlines():
        if line.startswith("RESULT,"):
            _, strat, t = line.split(",")
            times[strat] = float(t)
    missing = set(STRATEGIES) - set(times)
    if missing:
        raise RuntimeError(f"shards={shards}: no RESULT for {missing}")
    return times


def main():
    import jax

    if jax.default_backend() == "tpu":
        raise RuntimeError(
            "bench_scaling_shards is a host-forced CPU curve whose children "
            "cannot share this process's chip; on TPU run "
            "`python chip_smoke.py --four-chips` for the sharded check")
    n = scaled(10_000)
    g = watts_strogatz(n, 12, 1e-2, seed=0)
    ref = DeltaSteppingSolver(
        g, DeltaConfig(delta=_DELTA, strategy="edge",
                       pred_mode="none")).solve(0)
    cap = _probed_cap(g, ref.dist)
    times = {}
    for k in SHARD_COUNTS:
        for strat, t in _run_width(n, k, cap).items():
            times[(strat, k)] = t
            derived = f"shards={k}"
            if strat == "sharded_fused":
                derived += f";cap={cap}"
            row(f"scaling_shards/{strat}/k{k}", t, derived)
    # parallel efficiency, the sweep's derived quantity: E(k) =
    # T(1) / (k·T(k)) per strategy (gate=False: host-dependent trend)
    for strat in STRATEGIES:
        for k in SHARD_COUNTS[1:]:
            eff = times[(strat, 1)] / (k * times[(strat, k)])
            row(f"scaling_shards/{strat}/eff_k{k}", times[(strat, k)],
                f"shards={k};efficiency={eff:.3f}", gate=False)


if __name__ == "__main__":
    import sys as _sys

    _root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if _root not in _sys.path:
        _sys.path.insert(0, _root)
    main()
