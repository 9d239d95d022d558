"""The readers of the program's own spans and kernel names
(``chipbench.program_trace`` and the five metrics on it), checked on a
real TPU trace.

``data/l2svm_window.xplane.pb`` is the traced window of one
``l2svm-mnist8m-fit`` run on a TPU v5e at 32,768 rows (one whole
5-iteration fit, ``--seconds 0.05``); ``data/l2svm_window.json`` holds
what that run's process knew besides the trace (the kernel names it
registered, its span table) and the five metrics as it read them.
"""

import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from chipbench import plugins, program_trace as pt, trace as tr

DATA = Path(__file__).parent / "data"
XPLANE = DATA / "l2svm_window.xplane.pb"
RECORD = json.loads((DATA / "l2svm_window.json").read_text())
METRICS = ["pallas_ms_per_iter.fit", "sync_idle_ms_per_iter.fit",
           "dispatch_idle_ms_per_iter.fit", "syncs_per_iter.fit", "stage_s"]


@pytest.fixture
def run(monkeypatch):
    """A traced run of the fixture, with the program's registry and span
    table as they were in the process that recorded it."""
    from repro import obs
    monkeypatch.setattr(obs, "kernel_names",
                        lambda: frozenset(RECORD["kernel_names"]))
    monkeypatch.setattr(obs, "snapshot", lambda: RECORD["spans"])
    return SimpleNamespace(trace=True, workload="l2svm-mnist8m-fit",
                           window={"iterations": RECORD["iterations"]},
                           xplane=str(XPLANE))


@pytest.mark.parametrize("name", METRICS)
def test_metric_reads_what_the_chip_run_read(run, name):
    v = plugins.load_module("metrics", name).read(run)
    assert v == pytest.approx(RECORD["metrics"][name], rel=1e-12)


def test_the_fit_loop_readings(run):
    read = lambda n: plugins.load_module("metrics", n).read(run)
    assert read("syncs_per_iter.fit") == 8.0
    assert read("pallas_ms_per_iter.fit") > 0
    # the two host spans do not overlap, so their idle time is a part of
    # the window's idle time
    red = tr.reduce(*tr.read(str(XPLANE)))
    idle_ms = (red["window_s"] - red["busy_s"]) * 1e3 / RECORD["iterations"]
    host = read("sync_idle_ms_per_iter.fit") \
        + read("dispatch_idle_ms_per_iter.fit")
    assert 0 < host <= idle_ms
    assert 0 < read("stage_s") <= RECORD["spans"]["repro.stage"]["seconds"]


def test_every_registered_kernel_is_found(run):
    w = pt.load(run)
    found = {pt.op_kernel(o, RECORD["kernel_names"]) for o in w.ops}
    assert found - {None} == set(RECORD["kernel_names"])


def test_op_names_are_full_hlo_text_that_classify_misses():
    """A TPU trace names each operation by its HLO text, not a bare
    name.  ``chipbench.trace.classify`` matches bare names, so it reads
    no Pallas kernel and misses the relayout of X (``%copy = ...``):
    the case a repair of ``classify`` has to turn into ``kernel`` and
    ``relayout``."""
    ops, _spans = tr.read(str(XPLANE))
    names = set(RECORD["kernel_names"])
    kernels = [o for o in ops if pt.op_kernel(o, names)]
    x_copy = [o for o in ops if o.name.startswith("%copy = f32[32768,784]")]
    assert kernels and x_copy
    for o in kernels:
        assert " custom-call(" in o.name and o.name.startswith("%")
        assert tr.classify(o) == "other"
    assert {tr.classify(o) for o in x_copy} == {"other"}


def test_a_program_without_obs_reads_nothing(run, monkeypatch):
    monkeypatch.setattr(pt, "program_obs", lambda: None)
    for name in METRICS:
        assert plugins.load_module("metrics", name).read(run) is None


def test_idle_inside_spans_on_a_made_up_window():
    w = pt.Window(
        ops=[tr.Op(0, "%a = f32[] add()", 1.0, 2.0, ""),
             tr.Op(0, "%k_1.1 = f32[] custom-call()", 4.0, 5.0, "")],
        spans=[tr.Span("repro.sync", 0.5, 1.5),
               tr.Span("repro.sync", 2.5, 4.5),
               tr.Span("repro.call", 6.0, 7.0)],
        lo=0.0, hi=8.0, devices=[0])
    # idle: 0-1, 2-4, 5-8
    assert pt.idle_seconds_in(w, "repro.sync") == pytest.approx(0.5 + 1.5)
    assert pt.idle_seconds_in(w, "repro.call") == pytest.approx(1.0)
    assert pt.kernel_seconds(w, {"k_1"}) == pytest.approx(1.0)
    assert len(pt.spans_in(w, "repro.sync")) == 2


def test_lag_puts_every_program_after_its_enqueue():
    assert pt.lag([1.0, 2.0, 3.0], [1.3, 2.1, 3.2]) == pytest.approx(0.3)
    assert pt.lag([1.0, 2.0], [0.5, 1.5]) == 0.0      # already in order
    assert pt.lag([1.0, 2.0], [1.3]) == 0.0           # unpaired: no shift
    # the recorded window's clocks disagree by 1.35 ms (0.3-1.4 ms over
    # the v5e runs read so far)
    w = pt.read(str(XPLANE), "repro.")
    assert 0.0 < w.lag < 5e-3
