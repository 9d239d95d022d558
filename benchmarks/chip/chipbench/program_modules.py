"""Device time of the program's staged programs, by the fused region each
runs.

A program with ``repro.obs.program_names`` names the XLA module of each
staged whole-plan program after its region: ``plan_<region>``, with
``_bwd`` for the region's planned backward.  A TPU trace's ``XLA
Modules`` line holds one event per program run, named
``jit_<module>(<fingerprint>)``, whose duration covers every operation
of that program, XLA fusions included.  The events are moved later by
the lag ``program_trace`` found for the operations and cut to the
``bench.window`` span.

A program without that registry names no program: every reading is then
None, never 0.
"""

from __future__ import annotations

import re

from . import program_trace as pt
from . import trace as tr

_MODULE = re.compile(r"^jit_(\w+)\(")


def program_name(region) -> str:
    """The module name ``repro.obs.program_name`` gives the fused region
    ``region`` (a ``repro.core.fused`` object); ``_bwd`` is appended for
    its backward."""
    return "plan_" + re.sub(r"\W", "_", region.fn.__name__)


def load(run):
    """{module name: [(start, end), ...] over every device} of the traced
    window, read once per run; None without a trace or without the
    program's registry of program names."""
    obs = pt.program_obs()
    w = pt.load(run)
    if w is None or not hasattr(obs, "program_names"):
        return None
    got = getattr(run, "program_modules", None)
    if got is not None:
        return got
    from jax.profiler import ProfileData
    path = getattr(run, "xplane", None)
    if path is None:
        from .cell import TRACE_DIR
        path = tr.find_xplane(str(TRACE_DIR / run.workload))
    got = {}
    for plane in ProfileData.from_file(path).planes:
        if not tr.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name != pt.MODULES_LINE:
                continue
            for ev in line.events:
                m = _MODULE.match(ev.name)
                if m is None:
                    continue
                t0 = ev.start_ns * 1e-9 + w.lag
                a, b = max(t0, w.lo), min(t0 + ev.duration_ns * 1e-9, w.hi)
                if b > a:
                    got.setdefault(m.group(1), []).append((a, b))
    run.program_modules = got
    return got


def device_time(run, names):
    """(device seconds, runs) of the programs named ``names`` inside the
    window, averaged over the trace's devices; None where the program did
    not register one of the names."""
    mods = load(run)
    if mods is None:
        return None
    if not set(names) <= pt.program_obs().program_names():
        return None
    devices = max(1, len(pt.load(run).devices))
    spans = [s for n in names for s in mods.get(n, [])]
    return sum(b - a for a, b in spans) / devices, len(spans) / devices
