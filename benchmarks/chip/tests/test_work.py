"""Work counts are fixed by the equations, and ``mfu.fit`` is computed
from them and the table of peaks, not from the plan."""

import pytest

from chipbench import peaks, plugins

V5E = peaks.peaks_for("TPU v5 lite")


def _cfg(name):
    import json
    spec = plugins.bench_spec()
    entry = {c["name"]: c for c in spec["configs"]}[name]
    return json.loads((plugins.ROOT / entry["file"]).read_text())


def test_pass_counts_are_pinned():
    l2 = plugins.load_module("work", "l2svm")
    ml = plugins.load_module("work", "mlogreg")
    assert l2.passes_per_fit(_cfg("l2svm-mnist8m")) == 11
    assert ml.passes_per_fit(_cfg("mlogreg-mnist8m")) == 10
    assert l2.outer_per_fit(_cfg("l2svm-mnist8m")) == 5
    assert ml.outer_per_fit(_cfg("mlogreg-mnist8m")) == 2


def test_bytes_and_flops():
    l2, ml = _cfg("l2svm-mnist8m"), _cfg("mlogreg-mnist8m")
    x_bytes = 1011712 * 784 * 4
    wl2 = plugins.load_module("work", "l2svm")
    wml = plugins.load_module("work", "mlogreg")
    assert wl2.bytes_per_fit(l2) == 11 * x_bytes
    assert wml.bytes_per_fit(ml) == 10 * x_bytes
    # MLogReg: 10 columns, two products per pass -> 10 FLOP per byte
    assert wml.flops_per_fit(ml) / wml.bytes_per_fit(ml) == pytest.approx(10)
    assert wl2.flops_per_fit(l2) / wl2.bytes_per_fit(l2) == pytest.approx(1)


class _Run:
    def __init__(self, cfg, work, fits, seconds):
        self.config, self.work, self.peaks = cfg, work, V5E
        self.window = {"fits": fits, "seconds": seconds}


@pytest.mark.parametrize("config,algo", [("l2svm-mnist8m", "l2svm"),
                                          ("mlogreg-mnist8m", "mlogreg")])
def test_mfu_from_work_not_plan(config, algo):
    cfg = _cfg(config)
    work = plugins.load_module("work", algo)
    mfu = plugins.load_module("metrics", "mfu.fit")
    least = max(work.flops_per_fit(cfg) / V5E["peak_flops"],
                work.bytes_per_fit(cfg) / V5E["hbm_bw"])
    got = mfu.read(_Run(cfg, work, fits=7, seconds=3.0))
    assert got == pytest.approx(100 * 7 * least / 3.0)
    # bytes-bound at these shapes
    assert least == work.bytes_per_fit(cfg) / V5E["hbm_bw"]
    # the plan does not enter: another selection arm reads the same
    other = dict(cfg, mode="fa", pallas="never")
    assert mfu.read(_Run(other, work, 7, 3.0)) == got


def test_mfu_reads_nothing_without_a_chip():
    cfg = _cfg("l2svm-mnist8m")
    run = _Run(cfg, plugins.load_module("work", "l2svm"), 3, 1.0)
    run.peaks = None
    assert plugins.load_module("metrics", "mfu.fit").read(run) is None


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")
