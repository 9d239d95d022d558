"""The harness finds every part by name: a metric added as a file and an
entry is picked up with no other edit."""

import json
import shutil

from chipbench import cell, plugins
from conftest import HERE, ROOT, small


def test_every_named_part_exists():
    spec = plugins.bench_spec()
    for c in spec["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        plugins.load_module("algos", cfg["algorithm"])
        plugins.load_module("refs", cfg["algorithm"])
        plugins.load_module("work", cfg["algorithm"])
    for w in spec["workloads"]:
        tr = plugins.load_json("traffic", w["traffic"])
        plugins.load_module("generators", tr["generator"])
        plugins.load_json("limits", w["name"])
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(plugins.load_module("metrics", m["name"]).read)


def test_cell_metrics_follow_workloads():
    spec = plugins.bench_spec()
    names = lambda w, t: {m["name"] for m in
                          plugins.cell_metrics(spec, w, t)}
    assert names("l2svm-mnist8m-fit", False) == {"fit_iter_ms", "setup_s"}
    assert names("mlogreg-mnist8m-score", False) == {
        "score_p95_ms", "score_rows_per_s", "setup_s"}
    assert "mfu.fit" in names("mlogreg-mnist8m-fit", True)
    assert "mfu.fit" not in names("mlogreg-mnist8m-score", True)
    assert "score_batch_occupancy" in names("mlogreg-mnist8m-score", True)


def test_pending_cells_merge_by_name():
    """Cells of pending.json are found like those of BENCHMARK.json, and
    a metric named in both reports in the cells of both."""
    own = json.loads((ROOT / "BENCHMARK.json").read_text())
    pending = json.loads((HERE / "pending.json").read_text())
    spec = plugins.bench_spec()
    assert [w["name"] for w in spec["workloads"]] == [
        w["name"] for w in own["workloads"] + pending["workloads"]]
    fit = {m["name"]: m for m in spec["end_to_end"]}["fit_iter_ms"]
    assert set(fit["workloads"]) == {"l2svm-mnist8m-fit",
                                     "mlogreg-mnist8m-fit"}


def test_a_new_metric_file_is_picked_up(tmp_path):
    """Copy the benchmark, add one metric file and its entry, and run a
    cell: the new metric is in the result line."""
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("tests", ".out",
                                                  "__pycache__",
                                                  "pending.json"))
    spec = plugins.bench_spec()
    spec["end_to_end"].append(
        {"name": "dummy_fits", "unit": "count", "better": "higher",
         "bound": 0.01, "source": "host_clock",
         "workloads": ["l2svm-mnist8m-fit"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    (tmp_path / "benchmarks" / "chip" / "metrics" / "dummy_fits.py") \
        .write_text("def read(run):\n    return run.window['fits']\n")
    run = cell.prepare("l2svm-mnist8m-fit", 5, 0.3, False, root=tmp_path,
                       overrides=small("l2svm-mnist8m-fit"))
    res = cell.execute(run, t_start=0.0, require_chip=False)
    assert res["metrics"]["dummy_fits"] == {"value": run.window["fits"],
                                            "unit": "count"}
    assert res["metrics"]["dummy_fits"]["value"] >= 1
