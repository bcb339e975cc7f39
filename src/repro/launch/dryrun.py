"""Multi-pod dry-run driver (deliverable e).

For every (architecture × input-shape) cell, on the single-pod 16×16
mesh and the multi-pod 2×16×16 mesh:

    lowered  = jit(step, in_shardings=..., out_shardings=...).lower(*specs)
    compiled = lowered.compile()
    print(compiled.memory_analysis())   # proves it fits 16 GB/chip
    print(compiled.cost_analysis())     # FLOPs/bytes for §Roofline

and derives the §Roofline terms (compute/memory/collective) from the
compiled artifact. Results append to a JSON report consumed by
EXPERIMENTS.md. Failures (sharding mismatch, compile OOM, unsupported
collective) are bugs — the run exits nonzero listing them.

Usage:
  python -m repro.launch.dryrun                      # all cells, both meshes
  python -m repro.launch.dryrun --arch yi-34b --shape train_4k --mesh multi
  python -m repro.launch.dryrun --family lm --mesh single
  python -m repro.launch.dryrun --out reports.json --append

``main()`` backs the production meshes with 512 placeholder host
devices: it sets ``XLA_FLAGS`` before JAX initializes its backends, so
it must run in a fresh process (importing this module sets nothing).
The roofline terms are against the v5e target's peaks
(``TARGET_DEVICE_KIND``), not the host that compiles.
"""
import argparse
import json
import os
import sys
import time
import traceback

TARGET_DEVICE_KIND = "TPU v5 lite"


def run_cell(arch: str, shape_name: str, multi_pod: bool, hbm_limit_gb=16.0):
    from repro.analysis.roofline import analyze
    from repro.launch.cells import build_cell
    from repro.launch.mesh import make_production_mesh, mesh_name

    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    cell = build_cell(arch, shape_name, mesh)
    lowered = cell.lower()
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    report = analyze(
        compiled, arch=arch, shape=shape_name, mesh_name=mesh_name(mesh),
        n_devices=mesh.size, model_flops=cell.model_flops,
        device_kind=TARGET_DEVICE_KIND, note=cell.note)
    d = report.to_json()
    d["lower_s"] = round(t_lower, 1)
    d["compile_s"] = round(t_compile, 1)
    ma = d["memory_analysis"]
    per_dev = (ma.get("argument_size_in_bytes", 0)
               + ma.get("temp_size_in_bytes", 0)
               + ma.get("output_size_in_bytes", 0)
               - ma.get("alias_size_in_bytes", 0))
    d["hbm_bytes_per_dev"] = int(per_dev)
    d["fits_hbm"] = bool(per_dev <= hbm_limit_gb * 2**30)
    return d


def main():
    # jax locks the device count when its backends first initialize
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=512 "
        + os.environ.get("XLA_FLAGS", ""))
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--family", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--out", default="dryrun_reports.json")
    ap.add_argument("--append", action="store_true")
    ap.add_argument("--include-sssp", action="store_true",
                    help="also run the paper's SSSP configs")
    args = ap.parse_args()

    from repro.configs import all_cells, family_of

    cells = all_cells()
    if not args.include_sssp and args.arch is None and args.family is None:
        cells = [(a, s) for a, s in cells if not a.startswith("sssp-")]
    if args.arch:
        cells = [(a, s) for a, s in all_cells() if a == args.arch]
    if args.family:
        cells = [(a, s) for a, s in cells if family_of(a) == args.family]
    if args.shape:
        cells = [(a, s) for a, s in cells if s == args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    reports = []
    if args.append and os.path.exists(args.out):
        with open(args.out) as f:
            reports = json.load(f)
    done = {(r["arch"], r["shape"], r["mesh"]) for r in reports}

    failures = []
    for arch, shape in cells:
        for multi in meshes:
            mesh_label = "2x16x16" if multi else "16x16"
            if (arch, shape, mesh_label) in done:
                print(f"[skip] {arch} {shape} {mesh_label} (cached)")
                continue
            tag = f"{arch} × {shape} × {mesh_label}"
            print(f"[dryrun] {tag} ...", flush=True)
            try:
                d = run_cell(arch, shape, multi)
                reports.append(d)
                print(f"  ok: compile {d['compile_s']}s, "
                      f"hbm/dev {d['hbm_bytes_per_dev'] / 2**30:.2f} GiB "
                      f"(fits={d['fits_hbm']}), dominant={d['dominant']}, "
                      f"roofline={d['peak_fraction']:.1%}", flush=True)
                print(f"  memory_analysis: {d['memory_analysis']}")
                print(f"  cost: flops/dev={d['flops_per_dev']:.3e} "
                      f"bytes/dev={d['bytes_per_dev']:.3e} "
                      f"wire/dev={d['wire_bytes_per_dev']:.3e}")
            except Exception as e:
                failures.append((tag, repr(e)))
                traceback.print_exc()
            with open(args.out, "w") as f:
                json.dump(reports, f, indent=1)

    print(f"\n{len(reports)} cells compiled -> {args.out}")
    if failures:
        print(f"{len(failures)} FAILURES:")
        for tag, err in failures:
            print(f"  {tag}: {err[:200]}")
        sys.exit(1)


if __name__ == "__main__":
    main()
