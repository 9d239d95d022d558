"""Capture a profiler trace of the measured window and reduce it.

The reduction reads the ``.xplane.pb`` that ``jax.profiler`` writes with
``jax.profiler.ProfileData`` alone.  Device operations are the events of
each TPU plane's ``XLA Ops`` line; host spans are the events on the host
plane whose names start with ``bench.`` (``chipbench.spans``).  Both are
on one clock, so an idle gap on the device can be named by the span the
host was in.

Every operation falls in one of three classes, by its HLO name and
category as a TPU trace reports them:

* ``kernel``: a Pallas kernel, which XLA runs as a ``custom-call``;
* ``relayout``: a copy or transpose that only moves data between layouts;
* ``other``: every other XLA operation (fusions, reductions, dots).
"""

from __future__ import annotations

import contextlib
import glob
import os
import re
import shutil
from dataclasses import dataclass

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"

_RELAYOUT = re.compile(r"^(copy|transpose)([.-]|$)")
_KERNEL = re.compile(r"^custom-call([.-]|$)")


@dataclass(frozen=True)
class Op:
    device: int
    name: str
    start: float          # seconds
    end: float
    category: str         # the trace's hlo_category, "" where absent


@dataclass(frozen=True)
class Span:
    name: str
    start: float
    end: float


@contextlib.contextmanager
def capture(out_dir: str):
    """Trace the body into ``out_dir`` (emptied first).  The Python
    tracer stays off: it would time every Python call of the host loop
    the benchmark measures."""
    import jax
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir, exist_ok=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def find_xplane(out_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {out_dir}")
    return paths[-1]


def _stats(ev) -> dict:
    try:
        return dict(ev.stats)
    except (TypeError, ValueError):
        return {}


def read(path: str) -> tuple[list[Op], list[Span]]:
    """(device operations, host spans) of one trace file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops: list[Op] = []
    spans: list[Span] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    st = _stats(ev)
                    t0 = ev.start_ns * 1e-9
                    ops.append(Op(dev, ev.name, t0,
                                  t0 + ev.duration_ns * 1e-9,
                                  str(st.get("hlo_category", ""))))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        t0 = ev.start_ns * 1e-9
                        spans.append(Span(ev.name, t0,
                                          t0 + ev.duration_ns * 1e-9))
    return ops, spans


def classify(op: Op) -> str:
    name = op.name.lstrip("%")
    if _KERNEL.match(name) or op.category == "custom-call":
        return "kernel"
    if _RELAYOUT.match(name) or op.category == "data formatting":
        return "relayout"
    return "other"


def union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The union of ``intervals`` clipped to [lo, hi], sorted."""
    out: list[list[float]] = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _innermost(spans: list[Span], points: list[float]) -> list[str]:
    """For each time in ``points``, the shortest ``bench.*`` span (other
    than the window) that covers it, by one sweep over both."""
    edges = []
    for i, s in enumerate(spans):
        if s.name != WINDOW_SPAN:
            edges.append((s.start, 1, i))
            edges.append((s.end, 0, i))
    edges.sort()
    order = sorted(range(len(points)), key=points.__getitem__)
    out = ["host.other"] * len(points)
    active: set[int] = set()
    k = 0
    for q in order:
        t = points[q]
        while k < len(edges) and edges[k][0] <= t:
            _, opening, i = edges[k]
            (active.add if opening else active.discard)(i)
            k += 1
        if active:
            i = min(active, key=lambda j: spans[j].end - spans[j].start)
            out[q] = spans[i].name
    return out


def reduce(ops: list[Op], spans: list[Span], top: int = 10) -> dict:
    """Reduce one traced window.

    Returns ``window_s`` (the ``bench.window`` span), ``busy_s`` (the
    union of operation intervals inside it, averaged over devices),
    ``by_class`` (device seconds per class, summed over devices and
    divided by their number), ``n_ops``, and the breakdown: the ``top``
    operations by device time and the idle time inside the window
    grouped by the innermost host span at each gap's midpoint."""
    win = [s for s in spans if s.name == WINDOW_SPAN]
    if not win:
        raise ValueError("the trace holds no bench.window span")
    lo, hi = win[0].start, win[0].end
    devices = sorted({o.device for o in ops})
    if not devices:
        raise ValueError("the trace holds no device operation")
    nd = len(devices)
    busy = 0.0
    idle: list[tuple[float, float]] = []
    for d in devices:
        u = union(((o.start, o.end) for o in ops if o.device == d), lo, hi)
        busy += sum(b - a for a, b in u)
        edges = [lo] + [x for ab in u for x in ab] + [hi]
        idle += [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    gaps: dict[str, float] = {}
    for (a, b), who in zip(idle, _innermost(spans,
                                            [(a + b) / 2 for a, b in idle])):
        gaps[who] = gaps.get(who, 0.0) + (b - a) / nd
    by_class = {"kernel": 0.0, "relayout": 0.0, "other": 0.0}
    by_name: dict[str, float] = {}
    n_ops = 0
    for o in ops:
        a, b = max(o.start, lo), min(o.end, hi)
        if b <= a:
            continue
        n_ops += 1
        by_class[classify(o)] += (b - a) / nd
        by_name[o.name] = by_name.get(o.name, 0.0) + (b - a) / nd
    return {
        "window_s": hi - lo,
        "busy_s": busy / nd,
        "by_class": by_class,
        "n_ops": n_ops,
        "device_ops": sorted(([k, v] for k, v in by_name.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(([k, v] for k, v in gaps.items()),
                            key=lambda kv: -kv[1])[:top],
    }


def idle_percent(red: dict | None):
    """Percent of a reduced window in which no operation ran on the
    device; None without a trace."""
    if red is None or red["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
