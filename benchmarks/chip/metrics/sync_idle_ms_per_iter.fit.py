"""Device idle milliseconds per outer iteration inside the program's
``repro.sync`` spans: the round trip of the fit loops' blocking
device-to-host reads (from the trace)."""

from chipbench import program_trace as pt


def read(run):
    w = pt.load(run)
    if w is None:
        return None
    return pt.per_iter_ms(run, pt.idle_seconds_in(w, pt.program_obs().SYNC))
