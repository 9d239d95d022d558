"""Percent of HBM bandwidth the MLogReg fit's Hessian-vector products
reach: the bytes each requires (X and P read once,
``work/mlogreg_passes.py``) times the program's runs in the traced
window, over its device time there, as a share of the chip's peak
``hbm_bw``."""

from chipbench import plugins
from chipbench import program_modules as pm


def read(run):
    if run.peaks is None:
        return None
    regions = {r[0]: r[1] for r in run.algo.regions(run.config)}
    got = pm.device_time(run, [pm.program_name(regions["mlogreg.hvp"])])
    if got is None or not got[0]:
        return None
    seconds, calls = got
    work = plugins.load_module("work", "mlogreg_passes", run.base)
    least = calls * work.hvp_bytes(run.config) / run.peaks["hbm_bw"]
    return 100.0 * least / seconds
