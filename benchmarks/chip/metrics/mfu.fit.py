"""Share of the chip's peak the fits reach: the least time the fits'
required work could take on this chip, over the window's time.  The
least time of one fit is the larger of its required FLOPs over peak
FLOP/s and its required bytes over peak HBM bandwidth, as the
configuration's work functions count them from the equations (not from
the plan)."""


def least_fit_seconds(work, cfg: dict, peaks: dict) -> float:
    return max(work.flops_per_fit(cfg) / peaks["peak_flops"],
               work.bytes_per_fit(cfg) / peaks["hbm_bw"])


def read(run):
    w = run.window
    if not w.get("fits") or run.peaks is None:
        return None
    least = w["fits"] * least_fit_seconds(run.work, run.config, run.peaks)
    return 100.0 * least / w["seconds"]
