"""Device milliseconds per outer iteration in the program's Pallas
kernels: operations in the traced window whose HLO name carries a name
from the program's kernel registry (``repro.obs.kernel_names()``)."""

from chipbench import program_trace as pt


def read(run):
    w = pt.load(run)
    if w is None:
        return None
    names = pt.program_obs().kernel_names()
    return pt.per_iter_ms(run, pt.kernel_seconds(w, names))
