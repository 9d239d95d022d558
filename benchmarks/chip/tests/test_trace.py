"""The trace reduction: busy/idle union, the split of device operations
into kernels, relayout copies and other XLA operations, and idle gaps
named by the host span they fell in."""

from pathlib import Path

import pytest

from chipbench import trace as tr

#: a profile recorded on the CPU by ``trace.capture`` around two
#: annotated steps: real xplane bytes with host spans and no TPU plane
HOST_ONLY = Path(__file__).parent / "data" / "host_only.xplane.pb"


def test_union_clips_and_merges():
    assert tr.union([(0, 2), (1, 3), (5, 6), (9, 12)], 1, 10) == [
        (1, 3), (5, 6), (9, 10)]
    assert tr.union([(0, 1)], 2, 3) == []


def test_classify_by_name_and_category():
    op = lambda name, cat="": tr.Op(0, name, 0.0, 1.0, cat)
    assert tr.classify(op("custom-call.3")) == "kernel"
    assert tr.classify(op("copy.12")) == "relayout"
    assert tr.classify(op("transpose.4")) == "relayout"
    assert tr.classify(op("copy-start.1")) == "relayout"
    assert tr.classify(op("fusion.7")) == "other"
    assert tr.classify(op("copy_fusion", "data formatting")) == "relayout"
    assert tr.classify(op("convolution.2", "convolution")) == "other"


def test_reduce_on_a_made_up_window():
    ops = [tr.Op(0, "custom-call.1", 1.0, 2.0, ""),
           tr.Op(0, "copy.1", 2.0, 2.5, ""),
           tr.Op(0, "fusion.1", 4.0, 5.0, ""),
           tr.Op(0, "fusion.1", 9.5, 11.0, "")]
    spans = [tr.Span("bench.window", 0.0, 10.0),
             tr.Span("bench.fit", 0.0, 6.0),
             tr.Span("bench.submit", 2.6, 3.9)]
    r = tr.reduce(ops, spans)
    assert r["window_s"] == 10.0
    assert r["busy_s"] == pytest.approx(3.0)
    assert r["by_class"] == pytest.approx(
        {"kernel": 1.0, "relayout": 0.5, "other": 1.5})
    gaps = dict(r["idle_gaps"])
    # each gap goes whole to the span at its midpoint: 0-1 to
    # bench.fit, 2.5-4 to bench.submit, 5-9.5 to no span but the window
    assert gaps == pytest.approx({"bench.fit": 1.0, "bench.submit": 1.5,
                                  "host.other": 4.5})
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(1.5)]


def test_reduce_needs_the_window_span():
    with pytest.raises(ValueError):
        tr.reduce([tr.Op(0, "fusion.1", 0, 1, "")], [])


def test_read_a_recorded_trace():
    ops, spans = tr.read(str(HOST_ONLY))
    assert ops == []
    names = [s.name for s in spans]
    assert names.count("bench.window") == 1 and names.count("bench.fit") == 2
    win = next(s for s in spans if s.name == "bench.window")
    fits = [s for s in spans if s.name == "bench.fit"]
    assert all(win.start <= f.start < f.end <= win.end for f in fits)
    # with no device plane the reduction refuses rather than read 0 busy
    with pytest.raises(ValueError, match="no device operation"):
        tr.reduce(ops, spans)
