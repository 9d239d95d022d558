"""Device milliseconds per outer iteration spent in copies and
transposes that only change an array's layout (from the trace)."""


def read(run):
    t, w = run.trace_red, run.window
    if t is None or not w.get("iterations"):
        return None
    return t["by_class"]["relayout"] * 1e3 / w["iterations"]
