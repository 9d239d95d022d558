"""Percent of HBM bandwidth the MLogReg fit's probabilities, objective
and gradient pass reaches: the bytes one pass requires (X and Y read
once, P written once, ``work/mlogreg_passes.py``) times the passes in
the traced window (runs of the planned backward), over the device time
of the three programs that make a pass today (``_probs``, the fused
objective and its planned backward), as a share of the chip's peak
``hbm_bw``."""

from chipbench import plugins
from chipbench import program_modules as pm


def read(run):
    if run.peaks is None:
        return None
    regions = {r[0]: r[1] for r in run.algo.regions(run.config)}
    obj = pm.program_name(regions["mlogreg.objective"])
    names = [pm.program_name(regions["mlogreg.probs"]), obj, obj + "_bwd"]
    got = pm.device_time(run, names)
    passes = pm.device_time(run, [obj + "_bwd"])
    if got is None or passes is None or not got[0]:
        return None
    work = plugins.load_module("work", "mlogreg_passes", run.base)
    least = passes[1] * work.grad_bytes(run.config) / run.peaks["hbm_bw"]
    return 100.0 * least / got[0]
