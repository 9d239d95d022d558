"""Milliseconds per outer iteration of the fits in the window: the
window's wall time (first fit's start to the last fit's end, blocked on
its weights) over the outer iterations those fits completed."""


def read(run):
    w = run.window
    if not w.get("iterations"):
        return None
    return w["seconds"] * 1e3 / w["iterations"]
