#!/usr/bin/env python3
"""Read the numbers a cell's check compares, over many seeds, for the
program, for the control and for each planted fault, in one process.

    python3 benchmarks/chip/tools/readings.py --workload l2svm-mnist8m-fit \
        --seeds 101-112 --control-seeds 201-203 --fault-seeds 301-303 \
        --seconds 3

Each seed is one short run of the cell at its own size: set-up, a window
of ``--seconds`` through the window's own call, and the check.  One JSON
line per run gives the arm (``program``, ``control`` or ``fault:<kind>``),
the seed and every compared number.  The limits in ``limits/<cell>.json``
are set by hand from these readings: above the largest the program reads,
below the smallest the control (and each fault) reads.
"""

import argparse
import gc
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))


def seeds(text: str) -> list[int]:
    out = []
    for part in filter(None, text.split(",")):
        a, _, b = part.partition("-")
        out += list(range(int(a), int(b) + 1)) if b else [int(a)]
    return out


def main(argv=None, require_chip: bool = True, overrides=None) -> list:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--seconds", type=float, default=3.0)
    a = ap.parse_args(argv)

    from chipbench import cell

    arms = [("program", s) for s in seeds(a.seeds)]
    arms += [("control", s) for s in seeds(a.control_seeds)]
    probe = cell.prepare(a.workload, 0, a.seconds, False, overrides=overrides)
    for kind in probe.generator.FAULTS:
        arms += [(f"fault:{kind}", s) for s in seeds(a.fault_seeds)]
    out = []
    for arm, seed in arms:
        run = cell.prepare(a.workload, seed, a.seconds, False,
                           overrides=overrides)
        if arm == "control":
            run.generator.control(run)
        elif arm.startswith("fault:"):
            run.generator.fault(run, arm.split(":", 1)[1])
        t0 = time.perf_counter()
        res = cell.execute(run, t_start=t0, require_chip=require_chip)
        line = {"arm": arm, "seed": seed, "correct": res["correct"],
                "attempted": res["attempted"], "failed": res["failed"],
                **{k: c["value"] for k, c in res["checks"].items()},
                "run_s": time.perf_counter() - t0}
        print("READING " + json.dumps(line), flush=True)
        out.append(line)
        del run, res
        gc.collect()
    return out


if __name__ == "__main__":
    main()
    sys.exit(0)
