"""Device idle milliseconds per outer iteration inside the program's
``repro.call`` spans: the host binding and dispatching fused regions,
custom-VJP glue included (from the trace)."""

from chipbench import program_trace as pt


def read(run):
    w = pt.load(run)
    if w is None:
        return None
    return pt.per_iter_ms(run, pt.idle_seconds_in(w, pt.program_obs().CALL))
