#!/usr/bin/env python3
"""Find the knee of an open-loop scoring cell: the highest offered rate
at which the server completes what is offered with no growing queue.

    python3 benchmarks/chip/tools/knee.py --workload mlogreg-mnist8m-score \
        --rates 20,40,80,160 --seconds 10 --seed 7

One process sets the cell up once, then runs the window at each rate in
turn (the schedule for each rate is drawn as the generator draws it) and
prints one JSON line per rate: requests offered, completed inside the
window, the p50/p95 latency, and the median latency of the last quarter
of arrivals over that of the first quarter (a queue that grows through
the window reads well above 1).  The rate a cell uses is written into
its traffic file by hand from this output; the benchmark never searches.
"""

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))


def main() -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=0)
    a = ap.parse_args()

    import numpy as np
    from chipbench import cell, device, spans

    run = cell.prepare(a.workload, a.seed, a.seconds, False)
    cell.enable_cache()
    run.devs = device.require(run.cell["chips"])
    run.spans = spans.Spans(False)
    run.generator.setup(run)
    print(f"# set-up {time.perf_counter() - t_start:.3f} s", flush=True)
    try:
        for rate in (float(r) for r in a.rates.split(",")):
            run.state["schedule"] = run.generator.make_schedule(run, rate)
            run.window.clear()
            run.generator.window(run)
            w = run.window
            lat = np.asarray(w["latency_s"])
            n = len(lat)
            q = max(1, n // 4)
            done = int(np.sum(np.isfinite(lat)
                              & (lat + run.state["schedule"]["due"]
                                 <= a.seconds)))
            print(json.dumps({
                "rate": rate, "offered": n, "done_in_window": done,
                "rows_per_s": w["rows_done"] / a.seconds,
                "p50_ms": float(np.percentile(lat, 50) * 1e3),
                "p95_ms": float(np.percentile(lat, 95) * 1e3),
                "tail_growth": float(np.median(lat[-q:])
                                     / max(np.median(lat[:q]), 1e-9)),
                "late_max_ms": float(w["late_s"].max() * 1e3),
                "occupancy": w["occupancy"]}), flush=True)
    finally:
        run.generator.release(run)
    return 0


if __name__ == "__main__":
    sys.exit(main())
