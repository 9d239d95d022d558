"""One run of one cell: set-up, the measured window, the check against
the reference, and the result line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix, generator, limits and metrics are
all found by name from ``BENCHMARK.json``.  A run on a machine without
the chips the cell asks for exits non-zero before it prints a result.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

from . import plugins

#: JAX's persistent compilation cache, at a fixed path inside the
#: checkout (the path is part of every entry's key); an environment that
#: names its own directory keeps it
CACHE_DIR = plugins.ROOT / ".jax_cache"
TRACE_DIR = plugins.HERE / ".out" / "trace"


class Run:
    """What a generator and a metric reader see of one run."""

    def __init__(self, **kw):
        self.state: dict = {}       # the generator's own objects
        self.window: dict = {}      # what the window measured
        self.notes: list[str] = []  # lines for standard error
        self.hooks: dict = {}       # a control or a planted fault
        self.attempted = self.failed = 0
        self.trace_red = None
        self.__dict__.update(kw)

    def note(self, line: str) -> None:
        self.notes.append(line)
        print(f"# {line}", file=sys.stderr, flush=True)


def enable_cache() -> str:
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def prepare(workload: str, seed: int, seconds: float, trace: bool, *,
            root=None, overrides: dict | None = None) -> Run:
    """Look the cell up and load everything it names."""
    root = root or plugins.ROOT
    spec = plugins.bench_spec(root)
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"chipbench: no workload {workload!r} in "
                         f"BENCHMARK.json; known: {sorted(cells)}")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    with open(root / cfg_entry["file"]) as f:
        config = json.load(f)
    base = root / spec["paths"][0]
    traffic = plugins.load_json("traffic", cell["traffic"], base)
    limits = plugins.load_json("limits", workload, base)
    overrides = overrides or {}
    config.update(overrides.get("config", {}))
    traffic.update(overrides.get("traffic", {}))
    alg = config["algorithm"]
    return Run(spec=spec, base=base, workload=workload, cell=cell,
               config=config, traffic=traffic, limits=limits,
               seed=int(seed), seconds=float(seconds), trace=bool(trace),
               algo=plugins.load_module("algos", alg, base),
               refs=plugins.load_module("refs", alg, base),
               work=plugins.load_module("work", alg, base),
               generator=plugins.load_module("generators", traffic["generator"],
                                          base))


def execute(run: Run, *, t_start: float, require_chip: bool = True,
            trace_dir=None) -> dict:
    """Set up, measure, check and reduce; returns the result line."""
    import jax
    from . import device, spans
    from .peaks import peaks_for

    import repro  # noqa: F401 — the system under test; none, no run
    run.cache_dir = enable_cache()
    if require_chip:
        run.devs = device.require(run.cell["chips"])
        run.peaks = peaks_for(run.devs[0].device_kind)
    else:
        run.devs = jax.devices()[:run.cell["chips"]]
        run.peaks = None
    run.spans = spans.Spans(run.trace)
    run.mon = spans.CompileMonitor().install()
    try:
        try:
            with spans.planning_span(run.spans):
                run.generator.setup(run)
        except Exception as e:
            if "RESOURCE_EXHAUSTED" in str(e):
                from .memory import report
                report(run)
                raise SystemExit(f"chipbench: {run.workload} does not fit "
                                 f"the device: {str(e).splitlines()[0]}")
            raise
        run.setup_s = time.perf_counter() - t_start
        run.mon.phase = "window"
        if run.trace:
            from . import trace as tr
            out = str(trace_dir or TRACE_DIR / run.workload)
            with tr.capture(out):
                with run.spans.span(tr.WINDOW_SPAN):
                    run.generator.window(run)
            run.mon.phase = "after"
            ops, sp = tr.read(tr.find_xplane(out))
            run.trace_red = tr.reduce(ops, sp)
        else:
            run.generator.window(run)
        run.mon.phase = "after"
        dev = device.describe(run.devs)
        win = run.mon.get("window")
        run.note(f"setup {run.setup_s:.6f} s; set-up compiles "
                 f"{json.dumps(run.mon.get('setup'))}")
        run.note(f"window {run.window.get('seconds', 0):.6f} s; compiles "
                 f"inside the window {json.dumps(win)}")
        run.note(f"peak HBM in use {dev['memory_peak_bytes']} B")
        run.generator.release(run)
        readings = run.generator.check(run)
    finally:
        run.mon.uninstall()
    checks = {k: {"value": v, "limit": run.limits[k]}
              for k, v in readings.items()}
    correct = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
                  for c in checks.values())
    metrics = {}
    for m in plugins.cell_metrics(run.spec, run.workload, run.trace):
        v = plugins.load_module("metrics", m["name"], run.base).read(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    result = {"correct": correct, "attempted": run.attempted,
              "failed": run.failed, "metrics": metrics, "device": dev}
    if run.trace_red is not None:
        dev["busy_s"] = run.trace_red["busy_s"]
        dev["window_s"] = run.trace_red["window_s"]
        result["breakdown"] = {
            "device_ops": run.trace_red["device_ops"],
            "idle_gaps": run.trace_red["idle_gaps"]}
    for k, c in checks.items():
        print(f"check {k} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    result["checks"] = checks
    return result


def main(argv=None, t_start: float | None = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    run = prepare(a.workload, a.seed, a.seconds, bool(a.trace))
    result = execute(run, t_start=t_start)
    print(json.dumps(result), flush=True)
    return 0
