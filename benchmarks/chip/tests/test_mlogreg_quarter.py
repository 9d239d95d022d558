"""The ``mlogreg-mnist8m-quarter-fit`` cell: its configuration as the
harness finds it, its check at a small size on the CPU, the bytes its
two rooflines count, and its five readers on a synthetic window whose
busy and idle stretches are known."""

from pathlib import Path
from types import SimpleNamespace

import pytest

from chipbench import cell, plugins
from chipbench import program_trace as pt
from chipbench import trace as tr
from conftest import small

CELL = "mlogreg-mnist8m-quarter-fit"


def test_the_harness_runs_the_quarter_configuration():
    run = cell.prepare(CELL, 0, 1, False)
    assert run.cell["config"] == "mlogreg-mnist8m-quarter"
    cfg = run.config
    assert (cfg["rows"], cfg["features"], cfg["classes"]) == (2_023_424,
                                                              784, 10)
    assert (cfg["max_outer"], cfg["max_inner"]) == (2, 4)
    assert cfg["rows"] % 1024 == 0 and 4 * cfg["rows"] <= 8_100_000
    assert run.traffic["generator"] == "closed_fits"


@pytest.mark.parametrize("arm", ["program", "control", "unchanged",
                                 "half_batch", "altered"])
def test_check_passes_the_program_and_refuses_the_rest(arm):
    run = cell.prepare(CELL, 2**31 + 11, 0.6, False, overrides=small(CELL))
    if arm == "control":
        run.generator.control(run)
    elif arm != "program":
        run.generator.fault(run, arm)
    res = cell.execute(run, t_start=0.0, require_chip=False)
    assert res["correct"] == (arm == "program"), res["checks"]


def test_pass_bytes_at_the_cell_size():
    work = plugins.load_module("work", "mlogreg_passes")
    cfg = cell.prepare(CELL, 0, 1, False).config
    # X (2,023,424 x 784) and P (x 10) once
    assert work.hvp_bytes(cfg) == 4 * 2_023_424 * 794 == 6_426_394_624
    # X and Y read, P written
    assert work.grad_bytes(cfg) == 4 * 2_023_424 * 804 == 6_507_331_584


# a window of 1 s and 10 outer iterations, at 1,000 x 100 x 10
CFG = {"rows": 1000, "features": 100, "classes": 10}
HVP, PROBS, OBJ = "plan__hvp", "plan__softmax_probs_expr", "plan__nll_obj_reg"
MODULES = {
    HVP: [(0.10, 0.11), (0.12, 0.13), (0.14, 0.15), (0.16, 0.17)],
    PROBS: [(0.20, 0.21), (0.80, 0.81)],
    OBJ: [(0.21, 0.22), (0.81, 0.82)],
    OBJ + "_bwd": [(0.22, 0.24), (0.82, 0.84)],
    "plan_fn": [(0.90, 0.95)],
}
OPS = [(0.30, 0.35), (0.45, 0.50), (0.60, 0.62)]
SPANS = [("repro.cg", 0.30, 0.50), ("repro.grad", 0.60, 0.70)]
HBM = 88e6      # B/s: 4 HVPs of 440,000 B take 0.02 s at this rate


@pytest.fixture
def synthetic(monkeypatch):
    from repro import obs
    monkeypatch.setattr(obs, "program_names",
                        lambda: frozenset(MODULES) - {"plan_fn"})
    run = cell.prepare(CELL, 0, 1, True)
    run.config = dict(run.config, **CFG)
    run.peaks = {"hbm_bw": HBM}
    run.window = {"iterations": 10}
    run.program_window = pt.Window(
        [tr.Op(0, "op", a, b, "") for a, b in OPS],
        [tr.Span(n, a, b) for n, a, b in SPANS], 0.0, 1.0, [0])
    run.program_modules = MODULES
    return run


@pytest.mark.parametrize("name,expected", [
    ("hvp_ms_per_iter.fit", 4 * 10.0 / 10),
    ("hvp_roofline.fit", 100.0 * (4 * 4 * 1000 * 110 / HBM) / 0.04),
    ("grad_roofline.fit", 100.0 * (2 * 4 * 1000 * 120 / HBM) / 0.08),
    ("cg_idle_ms_per_iter.fit", (0.45 - 0.35) * 1e3 / 10),
    ("grad_idle_ms_per_iter.fit", (0.70 - 0.62) * 1e3 / 10),
])
def test_reader_on_a_synthetic_window(synthetic, name, expected):
    got = plugins.load_module("metrics", name).read(synthetic)
    assert got == pytest.approx(expected, rel=1e-9)
    if name.endswith("_roofline.fit"):
        assert 0 < got <= 100


@pytest.mark.parametrize("name", ["hvp_ms_per_iter.fit", "hvp_roofline.fit",
                                  "grad_roofline.fit",
                                  "cg_idle_ms_per_iter.fit",
                                  "grad_idle_ms_per_iter.fit"])
def test_reader_is_silent_where_the_program_marks_nothing(synthetic,
                                                          monkeypatch, name):
    """A program without the program registry and the two spans, as
    before they were added, reads None, not 0."""
    from repro import obs
    for attr in ("program_names", "CG", "GRAD"):
        monkeypatch.delattr(obs, attr)
    del synthetic.program_modules
    assert plugins.load_module("metrics", name).read(synthetic) is None


def test_modules_are_read_from_the_trace_by_name():
    """The module reader on a real TPU trace: every staged program of
    that L2SVM window ran as ``jit_plan_fn``, before programs were named
    by region; each run is cut to the window."""
    from chipbench import program_modules as pm
    run = SimpleNamespace(trace=True, workload="l2svm-mnist8m-fit",
                          xplane=str(Path(__file__).parent / "data"
                                     / "l2svm_window.xplane.pb"))
    mods = pm.load(run)
    w = pt.load(run)
    assert "plan_fn" in mods
    for spans in mods.values():
        assert all(w.lo <= a < b <= w.hi for a, b in spans)
