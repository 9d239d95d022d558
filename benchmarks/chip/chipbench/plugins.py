"""Find the benchmark's parts by name.

A configuration is ``configs/<name>.json``, a traffic mix
``traffic/<name>.json``, a cell's correctness limits
``limits/<workload>.json``.  Code parts are modules loaded from their
file: ``generators/<generator>.py`` (named by the traffic file; it
also brings its own control and planted faults),
``algos/<algorithm>.py``, ``refs/<algorithm>.py`` and
``work/<algorithm>.py`` (named by the configuration) and
``metrics/<metric>.py`` (named by ``BENCHMARK.json`` or
``pending.json``).  Adding any of
them is adding a file; no list here names one.
"""

from __future__ import annotations

import importlib.util
import json
import re
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]      # benchmarks/chip
ROOT = HERE.parents[1]                          # the checkout


def bench_spec(root: Path = ROOT) -> dict:
    """``BENCHMARK.json``, with the entries of ``<paths[0]>/pending.json``
    merged in: cells not yet proved on the chip, which the tools and
    tests run by name like any other.  A metric in both lists the cells
    of both."""
    with open(root / "BENCHMARK.json") as f:
        spec = json.load(f)
    path = root / spec["paths"][0] / "pending.json"
    if not path.is_file():
        return spec
    with open(path) as f:
        extra = json.load(f)
    for sec in ("configs", "workloads"):
        spec[sec] = spec[sec] + extra.get(sec, [])
    for sec in ("end_to_end", "per_layer"):
        have = {m["name"]: m for m in spec[sec]}
        for m in extra.get(sec, []):
            old = have.get(m["name"])
            if old is None:
                spec[sec].append(m)
            elif "workloads" in old:
                old["workloads"] = old["workloads"] + m["workloads"]
    return spec


def load_json(kind: str, name: str, base: Path = HERE) -> dict:
    path = base / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file {path}")
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str, base: Path = HERE):
    """The module in ``<base>/<kind>/<name>.py``, loaded once per
    process under a name of its own (metric names hold dots)."""
    path = base / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} module {path}")
    modname = "chipbench_" + kind + "_" + re.sub(r"\W", "_", name)
    mod = sys.modules.get(modname)
    if mod is not None and getattr(mod, "__file__", None) == str(path):
        return mod
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(spec: dict, workload: str, trace: bool) -> list[dict]:
    """The metric entries a run of ``workload`` reports: its end-to-end
    metrics with ``trace`` off, its per-layer metrics with it on.  A
    metric with a ``workloads`` key belongs to the cells it lists; one
    without it to every cell (end to end) or to every cell that reports
    the metric it moves (per layer)."""
    e2e = [m for m in spec["end_to_end"]
           if workload in m.get("workloads", [workload])]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if (workload in m["workloads"] if "workloads" in m
                else m["moves"] in names)]
