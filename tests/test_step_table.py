"""The staged lowering's step decisions, pinned per region.

For every fused region of ``repro.algos`` (the ``fusionlint`` registry,
at its shapes), mesh-free and under the abstract 4-way mesh that
``fusionlint`` plans with, in both Pallas modes, the golden
``tests/golden/exec_steps.json`` holds:

* the whole-plan keys, as ``sha256(repr(key))`` (``hash()`` differs
  between processes): ``staged_plan_key`` and the compiled plan's
  row-major lowering;
* ``plan_fallbacks``, ``row_orientations`` and ``explain()["execution"]``
  before a call;
* the compiled plan's recorded fallbacks after its lowering.

Node ids in reasons are rewritten to the node's position in the plan's
graph, so the golden does not depend on how many nodes earlier tests
made.  A change meant to move one of these regenerates the golden:

    REGEN_GOLDEN=1 PYTHONPATH=src python -m pytest tests/test_step_table.py
"""

import hashlib
import importlib.util
import json
import os
import re
from functools import lru_cache
from pathlib import Path

import pytest

from repro.core import fusion_mode
from repro.core.codegen import (plan_fallbacks, row_orientations,
                                staged_plan_key)

REPO = Path(__file__).resolve().parents[1]
GOLDEN = Path(__file__).parent / "golden" / "exec_steps.json"
ALGOS = ("l2svm", "mlogreg", "kmeans", "glm", "autoencoder", "als_cg")
LAYOUTS = ("local", "mesh[data=4]")
PALLAS = ("never", "interpret")


def _load_fusionlint():
    spec = importlib.util.spec_from_file_location(
        "fusionlint", REPO / "tools" / "fusionlint.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_LINT = _load_fusionlint()
REGIONS = [(algo, name) for algo in ALGOS for name, _w, _a in
           _LINT._cases(algo)]
CASES = [(a, r, lay, p) for a, r in REGIONS for lay in LAYOUTS
         for p in PALLAS]


@lru_cache(maxsize=None)
def _planned(algo: str, region: str, layout: str, pallas: str):
    wrapper, args = {n: (w, a) for n, w, a in _LINT._cases(algo)}[region]
    mesh = _LINT._mesh() if layout != "local" else None
    with fusion_mode("gen", layout=mesh, pallas=pallas, verify="off"):
        return wrapper.trace(**args).plan()


def _digest(key: tuple) -> str:
    return hashlib.sha256(repr(key).encode()).hexdigest()


def _record(algo: str, region: str, layout: str, pallas: str) -> dict:
    planned = _planned(algo, region, layout, pallas)
    eplan, lay = planned.eplan, planned.context.layout
    compiled = planned.compile()           # a fresh CompiledPlan
    execution = compiled.explain()["execution"]
    cp = compiled._cplan
    rec = {
        "plan_key": _digest(staged_plan_key(eplan, pallas=pallas)),
        "row_major_key": _digest(cp._staged_for(frozenset())[2]),
        "fallbacks": plan_fallbacks(eplan, layout=lay, pallas=pallas),
        "row_orientations": row_orientations(eplan, pallas=pallas,
                                             layout=lay),
        "execution": execution,
        "lowered_fallbacks": cp.fallbacks,
    }
    pos = {n.nid: i for i, n in enumerate(eplan.graph.nodes)}
    text = re.sub(r"%(\d+)",
                  lambda m: f"%n{pos.get(int(m.group(1)), '?')}",
                  json.dumps(rec, sort_keys=True))
    return json.loads(text)


def _name(algo, region, layout, pallas) -> str:
    return f"{algo}/{region}|{layout}|{pallas}"


@pytest.mark.parametrize("algo,region,layout,pallas", CASES,
                         ids=[_name(*c) for c in CASES])
def test_step_decisions_match_golden(algo, region, layout, pallas):
    want = json.loads(GOLDEN.read_text())[_name(algo, region, layout,
                                                pallas)]
    assert _record(algo, region, layout, pallas) == want


def test_plan_key_is_the_row_major_lowering_key():
    """``staged_plan_key`` (the server's bucketing identity and EXE004's
    check) is the key the mesh-free lowering caches its function under."""
    for algo, region in REGIONS:
        for pallas in PALLAS:
            planned = _planned(algo, region, "local", pallas)
            cp = planned.compile()._cplan
            assert staged_plan_key(planned.eplan, pallas=pallas) == \
                cp._staged_for(frozenset())[2], (algo, region, pallas)


if __name__ == "__main__" or os.environ.get("REGEN_GOLDEN"):
    GOLDEN.write_text(json.dumps(
        {_name(*c): _record(*c) for c in CASES}, indent=1,
        sort_keys=True) + "\n")
