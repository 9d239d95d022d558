"""Device milliseconds per outer iteration spent in Pallas kernels
(XLA custom calls, from the trace)."""


def read(run):
    t, w = run.trace_red, run.window
    if t is None or not w.get("iterations"):
        return None
    return t["by_class"]["kernel"] * 1e3 / w["iterations"]
