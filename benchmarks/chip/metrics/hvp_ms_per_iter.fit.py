"""Device milliseconds per outer iteration in the MLogReg fit's
Hessian-vector-product program (``_hvp``, one Row ``col_t_agg`` pass
over X per CG step), from the ``XLA Modules`` events of the traced
window (``chipbench.program_modules``)."""

from chipbench import program_modules as pm
from chipbench import program_trace as pt


def read(run):
    regions = {r[0]: r[1] for r in run.algo.regions(run.config)}
    got = pm.device_time(run, [pm.program_name(regions["mlogreg.hvp"])])
    return None if got is None else pt.per_iter_ms(run, got[0])
