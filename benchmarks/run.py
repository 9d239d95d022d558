"""Benchmark driver — one module per paper table/figure.

Usage::

    PYTHONPATH=src python -m benchmarks.run [--json PATH] [module ...]

Prints ``name,us_per_call,derived`` CSV rows; ``--json PATH`` additionally
writes every collected row as a machine-readable artifact (the CI uploads
``BENCH_fusion.json`` from the full job so the perf trajectory is
diffable across commits).  A module that fails to import or run is
reported and makes this script exit non-zero (no artifact is written).
"""

from __future__ import annotations

import sys
import time
import traceback

from .common import header, write_json

MODULES = [
    "micro_cell",        # Fig. 8(a,b)
    "micro_magg",        # Fig. 8(c,d)
    "micro_row",         # Fig. 8(e,g)
    "micro_outer",       # Fig. 8(h)
    "micro_compressed",  # Fig. 9
    "footprint",         # Fig. 10 (adapted)
    "dispatch_overhead",  # whole-plan vs per-operator dispatch
    "serving",           # FusionServer load test (throughput + tails)
    "compile_overhead",  # Table 3 / Fig. 11
    "plan_enum",         # Fig. 12
    "e2e_algos",         # Tables 4/5
    "e2e_distributed",   # Table 6 (shard_map over host devices)
]


def main() -> None:
    import importlib
    argv = sys.argv[1:]
    json_path = None
    if "--json" in argv:
        i = argv.index("--json")
        if i + 1 >= len(argv) or argv[i + 1].startswith("--"):
            sys.exit("usage: python -m benchmarks.run [--json PATH] "
                     "[module ...]")
        json_path = argv[i + 1]
        argv = argv[:i] + argv[i + 2:]
    want = argv or MODULES
    from repro import compile_cache
    compile_cache.enable()
    header()
    failed = []
    for name in want:
        t0 = time.perf_counter()
        print(f"# --- {name} ---", flush=True)
        try:
            importlib.import_module(f"benchmarks.{name}").main()
        except Exception:                  # noqa: BLE001 — reported below
            traceback.print_exc()
            failed.append(name)
            continue
        print(f"# {name} done in {time.perf_counter() - t0:.1f}s",
              flush=True)
    if failed:
        sys.exit(f"benchmark modules failed: {', '.join(failed)}")
    if json_path is not None:
        write_json(json_path, modules=want)


if __name__ == "__main__":
    main()
