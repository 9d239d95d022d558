"""The check that decides ``correct`` passes the program and refuses
the control and every planted fault, each driven through the whole run
(set-up, window, check) at a small size on the CPU."""

import pytest

from chipbench import cell
from conftest import small

CELLS = ["l2svm-mnist8m-fit", "mlogreg-mnist8m-fit", "mlogreg-mnist8m-score"]


def _run(workload, seed, arm=None):
    run = cell.prepare(workload, seed, 0.6, False, overrides=small(workload))
    if arm == "control":
        run.generator.control(run)
    elif arm is not None:
        run.generator.fault(run, arm)
    return cell.execute(run, t_start=0.0, require_chip=False)


@pytest.mark.parametrize("workload", CELLS)
def test_program_is_correct(workload):
    res = _run(workload, 2**31 + 11)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_refused(workload):
    res = _run(workload, 17, "control")
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("workload,kind", [
    (w, k) for w in CELLS
    for k in cell.prepare(w, 0, 1, False).generator.FAULTS])
def test_fault_is_refused(workload, kind):
    res = _run(workload, 23, kind)
    assert not res["correct"], (kind, res["checks"])


def test_result_line_shape():
    res = _run("l2svm-mnist8m-fit", 29)
    assert list(res)[-1] == "checks"
    assert set(res) >= {"correct", "attempted", "failed", "metrics",
                        "device"}
    assert set(res["metrics"]) == {"fit_iter_ms", "setup_s"}
    for c in res["checks"].values():
        assert set(c) == {"value", "limit"}
