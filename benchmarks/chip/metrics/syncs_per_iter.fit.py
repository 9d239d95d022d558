"""Blocking device-to-host reads per outer iteration: the program's
``repro.sync`` spans that start inside the traced window, over the
window's outer iterations."""

from chipbench import program_trace as pt


def read(run):
    w = pt.load(run)
    n = run.window.get("iterations")
    if w is None or not n:
        return None
    return len(pt.spans_in(w, pt.program_obs().SYNC)) / n
