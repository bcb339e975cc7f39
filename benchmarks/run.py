"""Benchmark driver: one module per paper table/figure. Prints
``name,us_per_call,derived`` CSV and writes one machine-readable
``BENCH_<name>.json`` perf record per bench module (rows + status), so
the performance trajectory across PRs can be diffed by tooling
(``benchmarks/check_regression.py`` gates CI on exactly these records).
Roofline terms come from the dry-run (launch.dryrun → EXPERIMENTS.md),
not from here.

Exit status is the CI contract: non-zero whenever any bench module
fails, so the gate can trust a green run (pinned by
tests/test_bench_gate.py). ``BENCH_SMOKE=1`` shrinks instances ~8x
(the gate regime); ``BENCH_OUT_DIR`` redirects the JSON records;
``--only smallworld,rmat`` restricts the module list.
"""
from __future__ import annotations

import argparse
import json
import os
import platform
import sys
import traceback


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, metavar="NAMES",
                    help="comma-separated bench names to run "
                         "(e.g. smallworld,rmat); default: all")
    args = ap.parse_args(argv)

    from benchmarks import (
        bench_delta_sweep,
        bench_dynamic,
        bench_gamemap,
        bench_multisource,
        bench_p2p,
        bench_policies,
        bench_preprocess,
        bench_queries,
        bench_rmat,
        bench_scaling,
        bench_scaling_shards,
        bench_serving,
        bench_sharded,
        bench_smallworld,
    )
    from benchmarks.common import SMOKE, drain_records

    modules = {}
    for mod in (bench_smallworld, bench_delta_sweep, bench_scaling,
                bench_preprocess, bench_rmat, bench_gamemap,
                bench_multisource, bench_sharded, bench_scaling_shards,
                bench_queries, bench_p2p, bench_dynamic, bench_serving,
                bench_policies):
        modules[mod.__name__.rsplit(".", 1)[-1].removeprefix("bench_")] = mod
    if args.only is not None:
        names = [s.strip() for s in args.only.split(",") if s.strip()]
        unknown = sorted(set(names) - set(modules))
        if unknown:
            print(f"unknown bench module(s) {unknown}; "
                  f"known: {sorted(modules)}", file=sys.stderr)
            return 2
        modules = {n: modules[n] for n in names}

    out_dir = os.environ.get("BENCH_OUT_DIR", ".")
    os.makedirs(out_dir, exist_ok=True)
    print("name,us_per_call,derived")
    failed = []
    for name, mod in modules.items():
        status = "ok"
        try:
            mod.main()
        except Exception:
            failed.append(mod.__name__)
            status = "failed"
            traceback.print_exc()
        record = {
            "bench": name,
            "status": status,
            "smoke": SMOKE,
            "python": platform.python_version(),
            "machine": platform.machine(),
            "rows": drain_records(),
        }
        path = os.path.join(out_dir, f"BENCH_{name}.json")
        with open(path, "w") as f:
            json.dump(record, f, indent=1)
    if failed:
        print(f"FAILED: {failed}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    # make `python benchmarks/run.py` work from anywhere: the bench
    # modules import as `benchmarks.*` from the repo root
    _root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if _root not in sys.path:
        sys.path.insert(0, _root)
    from repro.launch import compile_cache

    compile_cache.enable()
    sys.exit(main())
