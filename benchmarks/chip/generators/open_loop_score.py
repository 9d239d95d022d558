"""Open-loop multi-tenant scoring through one ``FusionServer``.

The traffic file fixes the server's settings, the tenants and their
Zipf skew, the range of rows per request (log-uniform) and the offered
rate (Poisson arrivals).  Every seed gets the same work: the
``round(rate * seconds)`` row counts and gaps between arrivals are the
quantiles of their distributions at (i + 0.5) / N, and the seed permutes
them, picks each request's tenant order and its slice of the request
pool.  The gaps are scaled so that the last request is due one gap
before the window's end.

Set-up builds the server, plans and compiles every row class the
schedule uses at every batch size (``FusionServer.warm``), and makes the
tenants' weights and a host pool of request rows from the seed; requests
are slices of that pool, never made in the window.

Each request is timed from the moment it was due to the moment its
result is on the host.  A request that fails, is refused, or has not
returned a minute after the window closed counts as infinitely late.
The check compares a sample of the window's answers, drawn from the
seed and always holding the largest request, with the float64
reference for the right tenant.
"""

from __future__ import annotations

import math
import threading
import time
from concurrent.futures import Future

import numpy as np

from chipbench.seeding import rng


def make_schedule(run, rate: float | None = None) -> dict:
    tr, seconds = run.traffic, run.seconds
    rate = float(tr["rate_per_s"] if rate is None else rate)
    n = max(1, int(round(rate * seconds)))
    g = rng(run.seed, 2)
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate
    gaps = g.permutation(gaps) * (seconds / gaps.sum())
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    lo, hi = math.log(tr["rows_min"]), math.log(tr["rows_max"])
    rows = np.rint(np.exp(lo + q * (hi - lo))).astype(np.int64)
    rows = g.permutation(rows)
    p = np.arange(1, tr["tenants"] + 1, dtype=np.float64) ** -tr["zipf_s"]
    p /= p.sum()
    counts = np.floor(p * n).astype(np.int64)
    for t in np.argsort(-(p * n - counts))[:n - counts.sum()]:
        counts[t] += 1
    tenant = g.permutation(np.repeat(np.arange(tr["tenants"]), counts))
    off = (g.random(n) * (tr["pool_rows"] - rows + 1)).astype(np.int64)
    k = min(n, tr["check_sample"])
    sample = {int(np.argmax(rows))}
    for i in g.permutation(n):
        if len(sample) >= k:
            break
        sample.add(int(i))
    return {"rate": rate, "due": due, "rows": rows, "tenant": tenant,
            "offset": off, "sample": sample}


def row_classes(rows, pad_to: int) -> list[int]:
    return sorted({int(-(-int(r) // pad_to) * pad_to) for r in rows})


def setup(run) -> None:
    import jax
    import jax.numpy as jnp
    from repro.core import FusionContext
    from repro.serve import FusionServer

    tr, cfg = run.traffic, run.config
    n, k = cfg["features"], cfg["classes"]
    g = rng(run.seed, 1)
    st = run.state
    st["B"] = [(g.standard_normal((n, k)) * tr["weight_scale"]
                / math.sqrt(n)).astype(np.float32)
               for _ in range(tr["tenants"])]
    st["pool"] = g.standard_normal((tr["pool_rows"], n), dtype=np.float32)
    st["schedule"] = make_schedule(run)
    st["region"] = run.algo.score_region()
    srv = FusionServer(workers=tr["workers"], max_batch=tr["max_batch"],
                       pad_to=tr["pad_to"],
                       context=FusionContext(mode=cfg["mode"],
                                             pallas=cfg["pallas"]))
    st["server"] = srv
    sizes, b = [], 1
    while b <= tr["max_batch"]:
        sizes.append(b)
        b *= 2
    classes = row_classes(st["schedule"]["rows"], tr["pad_to"])
    with run.spans.span("bench.warm"):
        srv.warm([(st["region"],
                   {"X": jax.ShapeDtypeStruct((r, n), jnp.float32),
                    "B": jax.ShapeDtypeStruct((n, k), jnp.float32)})
                  for r in classes], execute=True, batch_sizes=tuple(sizes))
    run.note(f"score set-up: {len(classes)} row classes x batch sizes "
             f"{sizes} warmed; {len(st['schedule']['due'])} requests at "
             f"{st['schedule']['rate']} /s")


def _submitter(run):
    hook = run.hooks.get("submit")
    if hook is not None:
        return hook
    st = run.state
    return lambda X, B: st["server"].submit(st["region"], X, B)


def window(run) -> None:
    st = run.state
    sch = st["schedule"]
    due, rows, ten, off = sch["due"], sch["rows"], sch["tenant"], sch["offset"]
    sample = sch["sample"]
    n = len(due)
    submit = _submitter(run)
    t_done = np.full(n, np.nan)
    late = np.zeros(n)
    failed = np.zeros(n, bool)
    answers: dict[int, object] = {}
    lock = threading.Lock()
    left = [n]
    all_done = threading.Event()

    def finish(i: int, ok: bool) -> None:
        t_done[i] = time.perf_counter()
        failed[i] = not ok
        with lock:
            left[0] -= 1
            if left[0] == 0:
                all_done.set()

    def on_done(i: int):
        def cb(fut):
            ok = fut.exception() is None
            if ok and i in sample:
                answers[i] = fut.result()
            finish(i, ok)
        return cb

    snap0 = _snapshot(run)
    t0 = time.perf_counter()
    for i in range(n):
        t_due = t0 + due[i]
        wait = t_due - time.perf_counter()
        if wait > 0:
            with run.spans.span("bench.gen_sleep"):
                time.sleep(wait)
        with run.spans.span("bench.submit"):
            t_sub = time.perf_counter()
            late[i] = t_sub - t_due
            X = st["pool"][off[i]:off[i] + rows[i]]
            try:
                fut = submit(X, st["B"][ten[i]])
            except Exception as e:      # noqa: BLE001 — a refused request
                run.state.setdefault("errors", []).append(repr(e))
                finish(i, False)
                continue
            fut.add_done_callback(on_done(i))
    t_close = t0 + run.seconds
    with run.spans.span("bench.result_wait"):
        all_done.wait(timeout=max(0.0, t_close + 60.0 - time.perf_counter()))
    snap1 = _snapshot(run)
    lat = np.where(failed | np.isnan(t_done), np.inf, t_done - (t0 + due))
    in_win = (~failed) & (t_done <= t_close)
    run.window.update(
        t0=t0, seconds=run.seconds, requests=n, latency_s=lat,
        rows_done=int(rows[in_win].sum()), late_s=late,
        occupancy=_delta_occupancy(snap0, snap1),
        server_compiles=_delta(snap0, snap1, ("compiles", "count")))
    st["answers"] = answers
    run.attempted = n
    run.failed = int(np.sum(~np.isfinite(lat)))
    run.note(f"generator lateness: max {late.max() * 1e3:.6f} ms, p95 "
             f"{np.percentile(late, 95) * 1e3:.6f} ms; requests failed or "
             f"unanswered {run.failed}; errors "
             f"{run.state.get('errors', [])[:3]}")
    if snap1 is not None:
        run.note("server: " + repr({k: snap1[k] for k in
                                    ("requests", "batches", "queue",
                                     "compiles")}))


def _snapshot(run):
    srv = run.state.get("server")
    return None if srv is None else srv.metrics.snapshot()


def _delta(s0, s1, path):
    if s0 is None or s1 is None:
        return None
    a, b = s0, s1
    for p in path:
        a, b = a[p], b[p]
    return b - a


def _delta_occupancy(s0, s1):
    nb = _delta(s0, s1, ("batches", "count"))
    if not nb:
        return None
    return _delta(s0, s1, ("requests", "completed")) / nb


def release(run) -> None:
    srv = run.state.pop("server", None)
    if srv is not None:
        srv.close()


def readings(run, answers=None) -> dict:
    """Against the float64 reference with the request's own tenant, the
    worst over the sampled requests of: ``probs_rel``, max |P - P_ref|
    over max |P_ref|; ``rowsum_gap``, max over rows of
    |sum_j P_ij - sum_j P_ref_ij| (each row of probabilities sums to one,
    so this reads the precision the probabilities are kept in, which the
    matrix product's rounding does not move); ``top_gap``, the widest gap
    by which the reference's log-probability of the class a row's answer
    ranks first lies below the reference's best; ``unanswered``, sampled
    requests with no answer or one of the wrong shape."""
    st = run.state
    sch = st["schedule"]
    answers = st["answers"] if answers is None else answers
    worst = gap = top = 0.0
    missing = 0
    for i in sorted(sch["sample"]):
        got = answers.get(i)
        r, o = int(sch["rows"][i]), int(sch["offset"][i])
        ref = run.refs.probs(st["pool"][o:o + r], st["B"][sch["tenant"][i]])
        if got is None or np.shape(got) != ref.shape:
            missing += 1
            continue
        got = np.asarray(got, np.float64)
        err = np.abs(got - ref).max() / max(np.abs(ref).max(), 1e-30)
        rs = np.abs(got.sum(axis=1) - ref.sum(axis=1)).max()
        lp = np.log(ref)
        pick = np.argmax(got, axis=1)
        tg = (lp.max(axis=1) - lp[np.arange(r), pick]).max()
        worst = max(worst, float(err) if np.isfinite(err) else math.inf)
        gap = max(gap, float(rs) if np.isfinite(rs) else math.inf)
        top = max(top, float(tg) if np.all(np.isfinite(got)) else math.inf)
    return {"probs_rel": worst, "rowsum_gap": gap, "top_gap": top,
            "unanswered": float(missing)}


def check(run) -> dict:
    return readings(run)


# The control and the planted faults that the check has to refuse.  Each
# only sets ``run.hooks``; the benchmark's own runs never call them (the
# tests and ``tools/readings.py`` do).

FAULTS = ("half_batch", "altered")


def _resolved(value) -> Future:
    f = Future()
    f.set_result(value)
    return f


def control(run) -> None:
    """The reference's probabilities, computed in the configuration's
    ``control_dtype`` on the device, in the server's place."""
    dt = run.config["control_dtype"]
    run.hooks["submit"] = lambda X, B: _resolved(run.refs.probs_lowp(X, B, dt))


def fault(run, kind: str) -> None:
    """Break the served answers underneath: ``altered`` serves another
    model (the classes' columns permuted), ``half_batch`` leaves half of
    a request's rows unscored."""
    if kind not in FAULTS:
        raise KeyError(f"open_loop_score has no fault {kind!r}")
    st = run.state

    def submit(X, B):
        if kind == "altered":
            return st["server"].submit(st["region"], X, B[:, ::-1].copy())
        inner = st["server"].submit(st["region"], X, B)
        outer = Future()

        def done(f):
            if f.exception() is not None:
                outer.set_exception(f.exception())
                return
            P = np.array(f.result(), copy=True)
            P[P.shape[0] // 2:] = 0.0
            outer.set_result(P)
        inner.add_done_callback(done)
        return outer
    run.hooks["submit"] = submit
