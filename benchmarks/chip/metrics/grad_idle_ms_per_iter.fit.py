"""Device idle milliseconds per outer iteration inside the program's
``repro.grad`` spans: the MLogReg fit's value-and-gradient call (the
fused forward's and the planned backward's dispatch and JAX's autodiff
glue between them), from the trace."""

from chipbench import program_trace as pt


def read(run):
    w = pt.load(run)
    name = getattr(pt.program_obs(), "GRAD", None)
    if w is None or name is None:
        return None
    return pt.per_iter_ms(run, pt.idle_seconds_in(w, name))
