#!/usr/bin/env python3
"""The control of the check that decides ``correct``: what the check
reads when the served answers are wrong in the way a later change would
be tempted to make them. Never part of a benchmark run.

    python3 chipbench/control.py --workload <cell> --seconds <s> \
        --seeds <n,n,...> [--narrow bfloat16,float16,int16] [--pred-none]

One process serves the cell's window once, as ``run.py`` does, and then
reads the check once per seed (a run's seed draws only which answers
are compared, so one window serves every seed):

* the program's own answers (the sound reading);
* ``--narrow``: each answer's distances replaced by the reference's
  held in a narrower type than the configuration's int32;
* ``--pred-none``: the window is served with the program's
  ``pred_mode='none'`` instead, which drops the predecessor tree and so
  the paths.

Each reading is one JSON line on standard output.
"""
from __future__ import annotations

import time

START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
from chipbench import run  # noqa: E402


def readings(cell, cfg, mix, *, seconds, seeds, narrow=(), pred_none=False,
             require_chip=True, cache_dir=run.CACHE_DIR, start=START):
    """``[{"control", "seed", "correct", "checks"}]``, or ``None`` when
    the device is not what the cell needs."""
    from chipbench import generator

    if pred_none:
        cfg = dict(cfg, engine=dict(cfg["engine"], pred_mode="none"))
    if run.open_device(cell, require_chip, cache_dir) is None:
        return None
    w = run.serve_window(cell, cfg, mix, seconds=seconds, trace=False,
                         start=start)
    hg = run.host_graph(w.dep)
    answered = [r for r in w.recs if r.failed is None]
    sets = [("pred_none" if pred_none else "program", None)]
    kind = generator.query_kind(mix)
    for dtype in narrow:
        t = time.monotonic()
        answers, changed = kind.narrowed(hg, answered, dtype)
        sets.append((dtype, answers))
        print(json.dumps({"narrowed": dtype, "distances_changed": changed,
                          "seconds": time.monotonic() - t}), flush=True)
    out = []
    for name, answers in sets:
        for seed in seeds:
            checks = run.check(hg, mix, w.recs, seed, answered=answers)
            out.append({"control": name, "seed": seed,
                        "correct": run.is_correct(checks),
                        "checks": {k: v for k, (v, _) in checks.items()}})
            print(json.dumps(out[-1]), flush=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds of the check")
    ap.add_argument("--narrow", default="",
                    help="comma-separated narrow types: bfloat16, float16, "
                    "int16")
    ap.add_argument("--pred-none", action="store_true")
    args = ap.parse_args(argv)
    err = run.prepare()
    if err:
        print(f"control.py: {err}", file=sys.stderr)
        return 2
    cell, cfg, mix, _ = run.load_cell(run.ROOT, args.workload)
    out = readings(cell, cfg, mix, seconds=args.seconds,
                   seeds=[int(s) for s in args.seeds.split(",")],
                   narrow=[d for d in args.narrow.split(",") if d],
                   pred_none=args.pred_none)
    return 1 if out is None else 0


if __name__ == "__main__":
    sys.exit(main())
