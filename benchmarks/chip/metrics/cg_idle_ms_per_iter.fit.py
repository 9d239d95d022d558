"""Device idle milliseconds per outer iteration inside the program's
``repro.cg`` spans: the MLogReg fit's inner CG steps (the HVP's
dispatch, its two blocking reads, the eager vector updates), from the
trace."""

from chipbench import program_trace as pt


def read(run):
    w = pt.load(run)
    name = getattr(pt.program_obs(), "CG", None)
    if w is None or name is None:
        return None
    return pt.per_iter_ms(run, pt.idle_seconds_in(w, name))
