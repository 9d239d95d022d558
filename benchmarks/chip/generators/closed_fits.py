"""Closed-loop fits: whole fits from zero weights, back to back, for the
window.

Set-up makes the data on the device and runs one whole fit, which plans
every region and compiles (or loads from the cache) every program the
window runs.  The window starts fits until the clock passes its length,
and ends when the last fit returns with its weights on the device.  The
check compares every fit of the window with the reference run once on
the same data after the window.
"""

from __future__ import annotations

import time

import numpy as np


def setup(run) -> None:
    run.state["data"] = run.algo.make_data(run.seed, run.config, run.devs[0])
    with run.spans.span("bench.warm_fit"):
        w, _ = _fit(run)
        w.block_until_ready()


def _fit(run):
    hook = run.hooks.get("fit")
    if hook is not None:
        return hook(run.state["data"], run.config)
    return run.algo.fit(run.state["data"], run.config)


def window(run) -> None:
    fits = []
    t0 = time.perf_counter()
    end = t0 + run.seconds
    while True:
        with run.spans.span("bench.fit"):
            w, objs = _fit(run)
            w.block_until_ready()
        fits.append((w, list(objs)))
        if time.perf_counter() >= end:
            break
    t1 = time.perf_counter()
    run.window.update(t0=t0, t1=t1, seconds=t1 - t0, fits=len(fits),
                      iterations=sum(len(o) for _, o in fits))
    run.state["fits"] = fits
    run.attempted, run.failed = len(fits), 0


def release(run) -> None:
    """Bring the window's weights to the host; the program keeps no
    other state between fits."""
    run.state["fits"] = [(np.asarray(w, np.float64), objs)
                         for w, objs in run.state["fits"]]


def readings(run, dtype: str = "float32") -> dict:
    """The numbers the check compares, worst over the window's fits:
    ``objective_rel`` (|objective - reference| / |reference| over every
    iteration), ``weights_rel`` (max |w - w_ref| / max |w_ref|) and
    ``iterations_off`` (fits whose iteration count differs)."""
    ref_w, ref_objs = run.algo.reference(run.state["data"], run.config,
                                         run.refs, dtype=dtype)
    ref_w = np.asarray(ref_w, np.float64)
    ref_objs = np.asarray(ref_objs, np.float64)
    scale = max(float(np.abs(ref_w).max()), 1e-30)
    obj_rel = w_rel = 0.0
    off = 0
    for w, objs in run.state["fits"]:
        objs = np.asarray(objs, np.float64)
        if objs.shape != ref_objs.shape:
            off += 1
            continue
        d = np.abs(objs - ref_objs) / np.abs(ref_objs)
        obj_rel = max(obj_rel, float(d.max()) if np.all(np.isfinite(d))
                      else float("inf"))
        e = np.abs(np.asarray(w, np.float64).reshape(ref_w.shape) - ref_w)
        w_rel = max(w_rel, float(e.max()) / scale if np.all(np.isfinite(e))
                    else float("inf"))
    return {"objective_rel": obj_rel, "weights_rel": w_rel,
            "iterations_off": float(off)}


def check(run) -> dict:
    return readings(run)


# The control and the planted faults that the check has to refuse.  Each
# only sets ``run.hooks``; the benchmark's own runs never call them (the
# tests and ``tools/readings.py`` do).

FAULTS = ("unchanged", "half_batch", "altered")


def control(run) -> None:
    """The reference, computed in the configuration's ``control_dtype``,
    in the program's place."""
    run.hooks["fit"] = lambda data, cfg: run.algo.reference(
        data, cfg, run.refs, dtype=cfg["control_dtype"])


def fault(run, kind: str) -> None:
    """Break the fit underneath: ``unchanged`` returns the state it was
    given, ``half_batch`` fits half of the rows and scales the sums from
    them, ``altered`` moves one weight by a tenth of the largest."""
    import jax.numpy as jnp
    if kind not in FAULTS:
        raise KeyError(f"closed_fits has no fault {kind!r}")

    def fit(data, cfg):
        if kind == "half_batch":
            m = next(iter(data.values())).shape[0] // 2
            w, objs = run.algo.fit({k: v[:m] for k, v in data.items()}, cfg)
            return w, [2.0 * o for o in objs]
        w, objs = run.algo.fit(data, cfg)
        if kind == "unchanged":
            w0 = jnp.zeros_like(w)
            return w0, [run.algo.objective(data, cfg, w0)] * len(objs)
        return w.at[0, 0].add(0.1 * jnp.abs(w).max()), objs
    run.hooks["fit"] = fit
