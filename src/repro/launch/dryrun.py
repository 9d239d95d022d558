"""Multi-pod dry-run: lower + compile every (architecture × input shape)
cell on the production 16×16 single-pod mesh and the 2×16×16 multi-pod
mesh, printing memory and cost analyses (the roofline inputs).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --all
  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-34b \
      --shape train_4k --mesh both [--layout auto]
"""

import argparse
import sys
import traceback


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both",
                    choices=("pod", "multipod", "both"))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--fusion", default="off")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--optimized", action="store_true",
                    help="apply the §Perf-winning variants: act=dp for "
                         "train/prefill, TP-only params + grouped GQA "
                         "for decode")
    ap.add_argument("--layout", default="fixed", choices=("fixed", "auto"),
                    help="auto: lower under the planner-searched layout "
                         "(repro.dist.planner) instead of the fixed rules")
    args = ap.parse_args()

    # the 512-host-device override must precede any jax backend init —
    # behind the main() guard (import-time flag mutation breaks any
    # host that imported jax first)
    from repro.launch import ensure_host_device_count
    ensure_host_device_count(512)

    from repro import compile_cache
    compile_cache.enable()
    from repro.configs import all_configs, cells
    from repro.launch.dryrun_lib import run_cell
    from repro.launch.mesh import make_production_mesh

    if args.all:
        todo = cells(all_configs())
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        todo = [(args.arch, args.shape)]

    meshes = []
    if args.mesh in ("pod", "both"):
        meshes.append(("pod16x16", make_production_mesh(multi_pod=False)))
    if args.mesh in ("multipod", "both"):
        meshes.append(("multipod2x16x16",
                       make_production_mesh(multi_pod=True)))

    from repro.configs import SHAPES
    failures = 0
    for arch, shape in todo:
        variant, vtag = None, ""
        if args.optimized:
            if SHAPES[shape].kind in ("train", "prefill"):
                variant, vtag = {"act": "dp"}, "opt"
            else:
                variant = {"serve_params": True, "gqa_grouped": True}
                vtag = "opt"
        for mesh_name, mesh in meshes:
            tag = f"{arch} × {shape} × {mesh_name}"
            try:
                rec = run_cell(arch, shape, mesh, mesh_name,
                               fusion=args.fusion, force=args.force,
                               variant=variant, variant_tag=vtag,
                               layout=args.layout)
                mem = rec["memory"]
                print(f"OK   {tag}: "
                      f"flops/dev={rec['flops_per_device']:.3e} "
                      f"bytes/dev={rec['bytes_per_device']:.3e} "
                      f"coll/dev={rec['collective_bytes_per_device']['total']:.3e} "
                      f"args={_gb(mem['argument_bytes'])} "
                      f"temp={_gb(mem['temp_bytes'])} "
                      f"(lower {rec['time_lower_s']}s, "
                      f"compile {rec['time_compile_s']}s)", flush=True)
            except Exception as e:
                failures += 1
                print(f"FAIL {tag}: {type(e).__name__}: {e}", flush=True)
                traceback.print_exc()
    return 1 if failures else 0


def _gb(x):
    return f"{x / 1e9:.2f}GB" if x is not None else "n/a"


if __name__ == "__main__":
    sys.exit(main())
