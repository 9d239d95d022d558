"""Set-up seconds in the program's ``repro.stage`` spans: the first call
of every staged plan at a new signature, where JAX traces it, lowers it
(the Pallas kernels through Mosaic included) and compiles it or loads it
from the compilation cache.  The program's span table over the run,
less any such span inside the traced window."""

from chipbench import program_trace as pt


def read(run):
    w = pt.load(run)
    if w is None:
        return None
    obs = pt.program_obs()
    total = obs.snapshot().get(obs.STAGE, {"seconds": 0.0})["seconds"]
    return total - sum(s.end - s.start for s in pt.spans_in(w, obs.STAGE))
