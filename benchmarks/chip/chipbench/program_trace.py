"""Read a traced window for the program's own spans and kernel names.

``chipbench.trace`` reduces the window by the benchmark's ``bench.*``
spans.  This module reads the same ``.xplane.pb`` again for what the
program marks itself (``repro.obs``): its ``repro.*`` host spans, and the
device operations whose HLO names carry a kernel name the program
registered.  Both are on one clock, so device idle time can be
attributed to the program span the host was in.

The profiler aligns the two clocks only to within a millisecond or so:
on a v5e the device's programs appear to start 0.2 to 1.4 ms before the
host began enqueueing them, by an amount that differs from run to run.
:func:`read` moves the device's events later by the least shift that
puts every program's start after the start of its enqueue (the lag), so
that an idle gap falls in the host span that caused it.

A program without ``repro.obs`` marks nothing: every reading here is
then None, never 0.
"""

from __future__ import annotations

import re
from dataclasses import dataclass

from . import trace as tr

#: the instruction name at the head of an op's name, which a TPU trace
#: gives as HLO text ("%row_no_agg_1f0c9a2e.1 = f32[...] custom-call(...)")
_HEAD = re.compile(r"^%?(\w+)")
#: the device line of whole programs, and the host event that enqueues
#: one program on a TPU
MODULES_LINE = "XLA Modules"
ENQUEUE = "DoEnqueueProgram"


def program_obs():
    """``repro.obs``, or None where the program has no such module."""
    try:
        from repro import obs
    except ImportError:
        return None
    return obs


@dataclass(frozen=True)
class Window:
    ops: list            # tr.Op, every device operation of the trace
    spans: list          # tr.Span, the program's spans
    lo: float            # the bench.window span
    hi: float
    devices: list
    lag: float = 0.0     # seconds the device's events were moved later


def lag(modules: list, enqueues: list) -> float:
    """The least shift that puts the start of every program on the device
    (``modules``) after the start of its enqueue on the host
    (``enqueues``), the two paired in order; 0 where they do not pair
    one to one."""
    if not modules or len(modules) != len(enqueues):
        return 0.0
    return max(0.0, max(q - m for m, q in zip(sorted(modules),
                                              sorted(enqueues))))


def read(path: str, prefix: str) -> Window:
    """Device operations and the host spans named under ``prefix`` of one
    trace file, bounded by its ``bench.window`` span, with the device's
    events moved later by the trace's :func:`lag` (one device only)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops: list[tr.Op] = []
    spans: list[tr.Span] = []
    modules: list[float] = []
    enqueues: list[float] = []
    window = None
    for plane in pd.planes:
        m = tr.DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    modules += [ev.start_ns * 1e-9 for ev in line.events]
                if line.name != tr.OPS_LINE:
                    continue
                for ev in line.events:
                    t0 = ev.start_ns * 1e-9
                    ops.append(tr.Op(int(m.group(1)), ev.name, t0,
                                     t0 + ev.duration_ns * 1e-9, ""))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == ENQUEUE:
                        enqueues.append(ev.start_ns * 1e-9)
                    if not (ev.name.startswith(prefix)
                            or ev.name == tr.WINDOW_SPAN):
                        continue
                    t0 = ev.start_ns * 1e-9
                    s = tr.Span(ev.name, t0, t0 + ev.duration_ns * 1e-9)
                    if s.name == tr.WINDOW_SPAN:
                        window = s
                    else:
                        spans.append(s)
    if window is None:
        raise ValueError(f"{path} holds no {tr.WINDOW_SPAN} span")
    devices = sorted({o.device for o in ops})
    shift = lag(modules, enqueues) if len(devices) == 1 else 0.0
    ops = [tr.Op(o.device, o.name, o.start + shift, o.end + shift,
                 o.category) for o in ops]
    return Window(ops, spans, window.start, window.end, devices, shift)


def load(run):
    """The run's traced window, read once per run; None without a trace
    or without ``repro.obs`` in the program."""
    obs = program_obs()
    if obs is None or not run.trace:
        return None
    w = getattr(run, "program_window", None)
    if w is None:
        path = getattr(run, "xplane", None)
        if path is None:
            from .cell import TRACE_DIR
            path = tr.find_xplane(str(TRACE_DIR / run.workload))
        w = run.program_window = read(path, obs.PREFIX)
    return w


def op_kernel(op: tr.Op, names) -> str | None:
    """The registered kernel name an operation carries, or None."""
    m = _HEAD.match(op.name)
    return m.group(1) if m and m.group(1) in names else None


def kernel_seconds(w: Window, names) -> float:
    """Device seconds inside the window in operations that carry one of
    ``names``, averaged over devices."""
    if not w.devices:
        return 0.0
    total = 0.0
    for o in w.ops:
        a, b = max(o.start, w.lo), min(o.end, w.hi)
        if b > a and op_kernel(o, names) is not None:
            total += b - a
    return total / len(w.devices)


def spans_in(w: Window, name: str) -> list:
    """The spans named ``name`` that start inside the window."""
    return [s for s in w.spans if s.name == name and w.lo <= s.start < w.hi]


def _intersect(xs, ys) -> float:
    """Total length of the overlap of two sorted, disjoint interval
    lists."""
    i = j = 0
    total = 0.0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if b > a:
            total += b - a
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_seconds_in(w: Window, name: str) -> float:
    """Device idle seconds inside the window that fall inside the spans
    named ``name``, averaged over devices."""
    if not w.devices:
        return 0.0
    inside = tr.union(((s.start, s.end) for s in w.spans if s.name == name),
                      w.lo, w.hi)
    total = 0.0
    for d in w.devices:
        busy = tr.union(((o.start, o.end) for o in w.ops if o.device == d),
                        w.lo, w.hi)
        edges = [w.lo] + [x for ab in busy for x in ab] + [w.hi]
        idle = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
        total += _intersect(idle, inside)
    return total / len(w.devices)


def per_iter_ms(run, seconds):
    """Milliseconds per outer iteration of the window's fits."""
    n = run.window.get("iterations")
    if seconds is None or not n:
        return None
    return seconds * 1e3 / n
