#!/usr/bin/env python3
"""Run the fusion engine's main path on a TPU and check what comes out.

    python chip_smoke.py               # one chip
    python chip_smoke.py --chips 4     # the distributed arm on four chips

One process drives every device it uses.  One chip: the L2SVM and MLogReg
fits over data with the Mnist8m width (784 features, one chip's quarter
of its 8.1M rows) in both ``pallas`` arms, proof that every fused region
ran a generated kernel, and ``FusionServer`` answering 48 scoring
requests.  ``--chips 4``: the same fits over the full Mnist8m shape,
row-sharded over a four-device mesh, through ``shard_map`` segments.
Every result is compared with a plain ``jax.numpy`` f32 implementation
run at ``highest`` matmul precision.

Any failed phase raises, so the script exits non-zero and prints no
result.  The last line of a passing run is one JSON object naming the
device, as JAX reports it.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

N_FEATURES, N_CLASSES = 784, 10          # Mnist8m width, digit classes
MNIST8M_ROWS = 8_100_000
LAM = 1e-3
L2SVM_ITERS, MLOGREG_OUTER, MLOGREG_INNER = 5, 2, 4
#: Default-precision f32 matmuls on the TPU round their operands to bf16
#: (relative error 2^-9 per product), and the CG iterations compound it:
#: a result within 5% of the reference's scale passes that rounding,
#: while a wrong kernel or a dropped tile is off by O(1) in the rows it
#: touches.  Objectives are compared relatively, weights and scores by
#: max |got - ref| over max |ref|.
TOL = 5e-2
N_REQUESTS = 48
#: the server's row classes are multiples of its pad_to (64); a reduced
#: fit keeps its rows a multiple of this so the kernels still tile
ROW_QUANTUM = 1024


def log(msg: str) -> None:
    print(msg, flush=True)


def fail(msg: str) -> None:
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


# --------------------------------------------------------------------------
# device
# --------------------------------------------------------------------------

def check_device(chips: int):
    import jax
    from repro import hw
    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"no TPU found: JAX runs on {devs[0].platform!r} "
             f"({devs[0].device_kind}); this check has no CPU fallback")
    if len(devs) < chips:
        fail(f"--chips {chips} needs {chips} TPU devices, found {len(devs)}")
    kind = devs[0].device_kind
    spec = hw.spec_for(kind)          # unknown kind: KeyError, not a default
    log(f"# device: {devs[0].platform} {kind!r} x{len(devs)}; spec "
        f"{spec.name}: {spec.peak_flops:.3g} FLOP/s, "
        f"{spec.hbm_bw:.3g} B/s HBM, {spec.hbm_bytes:.3g} B")
    return devs


# --------------------------------------------------------------------------
# the staged programs the fits run, compiled ahead of time
# --------------------------------------------------------------------------

def fit_regions(m: int):
    """(label, region, operand shapes, differentiated?) for every fused
    region ``l2svm.run`` and ``mlogreg.run`` call."""
    from repro.algos import l2svm, mlogreg
    n, k = N_FEATURES, N_CLASSES
    return [
        ("l2svm.objective", l2svm._objective_full,
         ((m, n), (n, 1), (m, 1), (1, 1)), True),
        ("l2svm.hinge", l2svm._hinge, ((m, n), (n, 1), (m, 1)), False),
        ("l2svm.search_terms", l2svm._search_terms, ((m, 1), (m, 1)), False),
        ("mlogreg.probs", mlogreg._probs, ((m, n), (n, k)), False),
        ("mlogreg.objective", mlogreg._nll_obj_reg,
         ((m, n), (n, k), (m, k), (1, 1)), True),
        ("mlogreg.hvp", mlogreg._hvp, ((m, n), (n, k), (m, k)), False),
    ]


def compile_programs(m: int, pallas: str, mesh=None) -> list[dict]:
    """Plan every fit region at ``m`` rows under ``pallas`` (and the
    mesh, if any), lower each staged program — forward and planned
    backward — and compile them for the devices without running them.
    XLA compiles the programs in parallel threads; the compile wall time
    is returned in each entry's ``compile_s``."""
    import jax
    import jax.numpy as jnp
    from concurrent.futures import ThreadPoolExecutor
    from jax.sharding import NamedSharding, PartitionSpec as P
    from jax.sharding import SingleDeviceSharding
    from repro.core import FusionContext

    ctx = FusionContext(mode="gen", pallas=pallas, layout=mesh)
    one = SingleDeviceSharding(jax.devices()[0])
    progs = []
    for label, region, shapes, differentiated in fit_regions(m):
        sds = [jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes]
        planned = region.trace(*sds).plan(context=ctx)
        compiled = planned.compile()
        stages = [(label, planned, compiled._cplan)]
        if differentiated:
            bwd, _names, _cts = compiled._get_bwd()
            stages.append((label + ".grad", planned.backward(), bwd))
        for name, pl, cp in stages:
            specs = getattr(cp.layout, "specs", {})
            args = [jax.ShapeDtypeStruct(
                        tuple(nd.shape), jnp.float32,
                        sharding=one if mesh is None else NamedSharding(
                            mesh, specs.get(nd.name) or P()))
                    for nd in cp.plan.graph.inputs()]
            fn, raw = cp.staged_callable()
            progs.append({"label": name, "planned": pl, "cplan": cp,
                          "raw": raw, "args": args,
                          "lowered": fn.lower(*args)})

    def build(p: dict) -> dict:
        try:
            exe = p.pop("lowered").compile()
        except Exception as e:              # noqa: BLE001 — sized below
            if "RESOURCE_EXHAUSTED" not in str(e):
                raise
            return {"label": p["label"], "oom": str(e).splitlines()[0]}
        ma = exe.memory_analysis()
        return dict(p, hlo_kernels=exe.as_text().count("tpu_custom_call"),
                    memory=ma, bytes=(ma.argument_size_in_bytes
                                      + ma.output_size_in_bytes
                                      + ma.temp_size_in_bytes
                                      - ma.alias_size_in_bytes))

    t0 = time.perf_counter()
    with ThreadPoolExecutor(len(progs)) as pool:
        out = list(pool.map(build, progs))
    secs = time.perf_counter() - t0
    return [dict(p, compile_s=secs) for p in out]


def size_rows(m: int, mesh, budget: float):
    """Halve the per-device rows ``m`` until every program of the
    ``pallas="tpu"`` arm fits ``budget`` bytes per device.  Returns
    (rows per device, the compiled programs, seconds spent compiling)."""
    t_compile = 0.0
    while True:
        progs = compile_programs(m * (1 if mesh is None else mesh.size),
                                 "tpu", mesh)
        t_compile += progs[0]["compile_s"]
        for p in progs:
            if "oom" in p:
                log(f"#   {p['label']} @ {m} rows/device: {p['oom']}")
            else:
                ma = p["memory"]
                log(f"#   {p['label']} @ {m} rows/device: memory_analysis args "
                    f"{ma.argument_size_in_bytes} B, out "
                    f"{ma.output_size_in_bytes} B, temp "
                    f"{ma.temp_size_in_bytes} B, alias "
                    f"{ma.alias_size_in_bytes} B")
        worst = max(float("inf") if "oom" in p else p["bytes"]
                    for p in progs)
        if worst <= budget:
            return m, progs, t_compile
        half = m // 2 // ROW_QUANTUM * ROW_QUANTUM
        log(f"# fit check: a step needs {worst:.4g} B per device > budget "
            f"{budget:.4g} B at {m} rows; REDUCED to {half} rows")
        if half < ROW_QUANTUM:
            fail("no row count fits the device")
        m = half


# --------------------------------------------------------------------------
# data, made on the device from the seed
# --------------------------------------------------------------------------

def make_data(seed: int, rows: int, sharding):
    """X (rows, 784) ~ N(0, 1); labels from a random linear model plus
    noise: Y one-hot over 10 classes, y = +1 for class 0 else -1."""
    import jax
    import jax.numpy as jnp
    n, k = N_FEATURES, N_CLASSES

    def gen(key):
        kx, kw, ke = jax.random.split(key, 3)
        X = jax.random.normal(kx, (rows, n), jnp.float32)
        W = jax.random.normal(kw, (n, k), jnp.float32) / jnp.sqrt(n)
        logits = X @ W + 0.5 * jax.random.normal(ke, (rows, k), jnp.float32)
        cls = jnp.argmax(logits, axis=1)
        Y = jax.nn.one_hot(cls, k, dtype=jnp.float32)
        y = jnp.where(cls == 0, 1.0, -1.0).astype(jnp.float32)[:, None]
        return X, Y, y

    out = jax.jit(gen, out_shardings=(sharding,) * 3)(jax.random.key(seed))
    return jax.block_until_ready(out)


# --------------------------------------------------------------------------
# plain jax.numpy reference implementations (highest matmul precision)
# --------------------------------------------------------------------------

# (the data are arguments of every jitted step: a jitted function that
# closed over X would embed it in the program as a constant)

def ref_l2svm(X, y, lam, iters):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def grad_obj(X, y, w):
        out = jnp.maximum(1.0 - y * (X @ w), 0.0)
        obj = 0.5 * jnp.sum(out ** 2) + 0.5 * lam * jnp.sum(w ** 2)
        return obj, -(X.T @ (out * y)) + lam * w

    @jax.jit
    def line_terms(X, y, w, s):
        Xs = X @ s
        out = jnp.maximum(1.0 - y * (X @ w), 0.0)
        act = (out > 0).astype(jnp.float32)
        yXs = y * Xs
        num = jnp.sum(act * out * yXs) - lam * jnp.sum(w * s)
        den = jnp.sum(act * yXs * yXs) + lam * jnp.sum(s * s)
        return num, den

    with jax.default_matmul_precision("highest"):
        w = jnp.zeros((X.shape[1], 1), jnp.float32)
        _, g = grad_obj(X, y, w)
        s, objs = -g, []
        for _ in range(iters):
            num, den = line_terms(X, y, w, s)
            w = w + float(num) / max(float(den), 1e-30) * s
            obj, g_new = grad_obj(X, y, w)
            objs.append(float(obj))
            beta = float(jnp.sum(g_new ** 2)) / max(float(jnp.sum(g ** 2)),
                                                    1e-30)
            s, g = -g_new + beta * s, g_new
    return w, objs


def ref_mlogreg(X, Y, lam, outer, inner):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def probs_obj_grad(X, Y, B):
        Z = X @ B
        E = jnp.exp(Z - Z.max(axis=1, keepdims=True))
        P = E / E.sum(axis=1, keepdims=True)
        obj = -jnp.sum(Y * jnp.log(P + 1e-30)) + 0.5 * lam * jnp.sum(B * B)
        return P, obj, X.T @ (P - Y) + lam * B

    @jax.jit
    def hvp(X, P, p):
        Q = P * (X @ p)
        return X.T @ (Q - P * Q.sum(axis=1, keepdims=True)) + lam * p

    with jax.default_matmul_precision("highest"):
        B = jnp.zeros((X.shape[1], Y.shape[1]), jnp.float32)
        objs = []
        for _ in range(outer):
            P, obj, G = probs_obj_grad(X, Y, B)
            objs.append(float(obj))
            d, r = jnp.zeros_like(B), -G
            p, rs = r, float(jnp.sum(r * r))
            for _ in range(inner):
                Hp = hvp(X, P, p)
                alpha = rs / max(float(jnp.sum(p * Hp)), 1e-30)
                d, r = d + alpha * p, r - alpha * Hp
                rs_new = float(jnp.sum(r * r))
                if rs_new < 1e-12:
                    break
                p, rs = r + (rs_new / rs) * p, rs_new
            B = B + d
    return B, objs


# --------------------------------------------------------------------------
# checks
# --------------------------------------------------------------------------

def rel_err(got, ref) -> float:
    import numpy as np
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    return float(np.abs(got - ref).max() / max(np.abs(ref).max(), 1e-30))


def compare(label: str, objs, w, ref_objs, ref_w) -> None:
    import numpy as np
    if len(objs) != len(ref_objs):
        fail(f"{label}: {len(objs)} iterations, reference ran "
             f"{len(ref_objs)}")
    obj_rel = np.abs(np.subtract(objs, ref_objs)) / np.abs(ref_objs)
    w_err = np.abs(np.asarray(w, np.float64) - np.asarray(ref_w))
    w_rel = rel_err(w, ref_w)
    log(f"# {label}: objectives {objs}")
    log(f"#   reference        {ref_objs}")
    log(f"#   objective max rel err {obj_rel.max():.3e}; weights max abs "
        f"err {w_err.max():.3e}, max rel err {w_rel:.3e} (tol {TOL})")
    if not np.all(np.isfinite(objs)) or obj_rel.max() > TOL or w_rel > TOL:
        fail(f"{label} is off the reference beyond tol {TOL}")


def check_kernels(progs, mesh) -> None:
    """Every fused operator the dispatch table gives a Pallas kernel
    (dense Cell / Row / MAgg) must show one ``tpu_custom_call`` in the
    compiled program, unless a fallback names why it takes its XLA body;
    every fallback must carry a reason.  Under a mesh the kernels must
    sit inside the ``shard_map`` regions and the fits must have
    distributed segments."""
    import jax
    n_segments = 0
    for p in progs:
        sigs = p["planned"].fused_signatures()
        fbs = p["planned"].explain()["execution"]["fallbacks"] \
            + p["cplan"].fallbacks
        eligible = sum(1 for s in sigs
                       if s["template"].split("(")[0] in ("CELL", "ROW",
                                                          "MAGG"))
        kernel_fbs = [f for f in fbs if f["site"] == "kernel"]
        line = (f"#   {p['label']}: templates "
                f"{[s['template'] for s in sigs]}, tpu_custom_call "
                f"{p['hlo_kernels']}, fallbacks {fbs}")
        if mesh is not None:
            segs = p["planned"].explain()["distributed"]["segments"]
            n_segments += len(segs)
            inside = _kernels_in_shard_map(
                jax.make_jaxpr(p["raw"])(*p["args"]).jaxpr)
            line += f", segments {len(segs)}, kernels in shard_map {inside}"
            if inside < eligible:
                fail(f"{p['label']}: {eligible} distributed kernels, only "
                     f"{inside} inside shard_map")
            if fbs:
                fail(f"{p['label']}: fallbacks on the distributed arm: {fbs}")
        log(line)
        if any(not str(f.get("reason", "")).strip() for f in fbs):
            fail(f"{p['label']}: a fallback without a reason: {fbs}")
        if p["hlo_kernels"] < eligible - len(kernel_fbs) or (
                eligible and not p["hlo_kernels"]):
            fail(f"{p['label']}: {eligible} Pallas-eligible operators "
                 f"but {p['hlo_kernels']} kernels in the compiled HLO")
    if mesh is not None and not n_segments:
        fail("no distributed segments in any fit program")


def _kernels_in_shard_map(jaxpr, inside: bool = False) -> int:
    """Count ``pallas_call`` equations nested under a ``shard_map``."""
    import jax
    n = 0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "pallas_call" and inside:
            n += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _kernels_in_shard_map(sub, inside or name == "shard_map")
    return n


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------

def run_fits(X, Y, y, arms, timings) -> None:
    from repro.algos import l2svm, mlogreg
    ref_w, ref_l2 = ref_l2svm(X, y, LAM, L2SVM_ITERS)
    ref_B, ref_ml = ref_mlogreg(X, Y, LAM, MLOGREG_OUTER, MLOGREG_INNER)
    for pallas, layout in arms:
        tag = f"pallas={pallas}" + ("" if layout is None else ", 4-chip mesh")
        for algo, fit, ref in (
                ("l2svm", lambda: l2svm.run(
                    X, y, lam=LAM, max_iter=L2SVM_ITERS, mode="gen",
                    pallas=pallas, layout=layout), (ref_l2, ref_w)),
                ("mlogreg", lambda: mlogreg.run(
                    X, Y, lam=LAM, max_outer=MLOGREG_OUTER,
                    max_inner=MLOGREG_INNER, mode="gen", pallas=pallas,
                    layout=layout), (ref_ml, ref_B))):
            secs = []
            for _ in range(2):           # cold (compiles), then warm
                t0 = time.perf_counter()
                w, objs = fit()
                w.block_until_ready()
                secs.append(time.perf_counter() - t0)
            timings[f"{algo} [{tag}]"] = secs
            log(f"# {algo} [{tag}]: first run {secs[0]:.3f} s (compiles), "
                f"second run {secs[1]:.3f} s")
            compare(f"{algo} [{tag}]", objs, w, ref[0], ref[1])


def run_serving(seed: int) -> None:
    import numpy as np
    from repro.algos import l2svm, mlogreg
    from repro.core import FusionContext
    from repro.serve import FusionServer

    rng = np.random.default_rng(seed)
    n, k = N_FEATURES, N_CLASSES
    w = (rng.standard_normal((n, 1)) / np.sqrt(n)).astype(np.float32)
    B = (rng.standard_normal((n, k)) / np.sqrt(n)).astype(np.float32)
    t0 = time.perf_counter()
    with FusionServer(workers=2, context=FusionContext(pallas="tpu")) as srv:
        jobs = []
        for i in range(N_REQUESTS):
            rows = int(rng.integers(1000, 32769))
            Xr = rng.standard_normal((rows, n), dtype=np.float32)
            if i % 2 == 0:
                yr = rng.choice(np.float32([-1.0, 1.0]), size=(rows, 1))
                ref = np.maximum(1.0 - yr * (Xr.astype(np.float64) @ w), 0.0)
                jobs.append(("hinge", rows, srv.submit(l2svm._hinge, Xr, w,
                                                       yr), ref))
            else:
                Z = Xr.astype(np.float64) @ B
                E = np.exp(Z - Z.max(axis=1, keepdims=True))
                ref = E / E.sum(axis=1, keepdims=True)
                jobs.append(("probs", rows, srv.submit(mlogreg._probs, Xr, B),
                             ref))
        errs = []
        for kind, rows, fut, ref in jobs:
            got = fut.result(timeout=900)
            if got.shape != ref.shape:
                fail(f"serving {kind}[{rows}]: shape {got.shape}, "
                     f"expected {ref.shape}")
            errs.append(rel_err(got, ref))
        report = srv.metrics.report(srv)
    secs = time.perf_counter() - t0
    s = report["serving"]
    log(f"# serving: {len(jobs)} requests, rows "
        f"{min(j[1] for j in jobs)}..{max(j[1] for j in jobs)}, "
        f"max rel err {max(errs):.3e} (tol {TOL}), {secs:.3f} s wall, "
        f"{s['compiles']['count']} plan compiles "
        f"({s['compiles']['time_s']:.3f} s), batches {s['batches']}")
    log(f"#   resilience {s['resilience']}, runtime_fallbacks "
        f"{s['runtime_fallbacks']}")
    bad = [i for i, e in enumerate(errs) if not e <= TOL]
    if bad:
        fail(f"serving: requests {bad} off the reference beyond tol {TOL}")
    res = s["resilience"]
    counters = {"degraded": res["degraded"], "bisections": res["bisections"],
                "breaker": res["breaker"], "rejected": res["rejected"],
                "retries_exhausted": res["retries_exhausted"],
                "nonfinite": res["nonfinite_detected"],
                "worker_crashes": res["workers"]["crashes"],
                "runtime_fallbacks": s["runtime_fallbacks"],
                "failed_dispatches": s["batches"]["failed_dispatches"],
                "pad_fallbacks": s["batches"]["pad_fallbacks"],
                "quarantined": report["server"]["breaker"]["quarantined"]}
    nonzero = {k: v for k, v in counters.items() if v}
    if nonzero:
        fail(f"serving took a degraded or failed path: {nonzero}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    t_start = time.perf_counter()
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from jax.sharding import SingleDeviceSharding
    from repro import compile_cache, hw

    devs = check_device(args.chips)
    log(f"# compile cache: {compile_cache.enable()}")
    mesh = None
    if args.chips == 4:
        mesh = Mesh(np.array(devs[:4]).reshape(4), ("data",))
    stats = devs[0].memory_stats() or {}
    limit = stats.get("bytes_limit") or hw.spec_for(
        devs[0].device_kind).hbm_bytes
    budget = hw.spec_for(devs[0].device_kind).hbm_usable * limit
    rows_dev = MNIST8M_ROWS // 4        # one chip's share of the 4-way split
    log(f"# fit check: compile every fit program at {rows_dev} rows per "
        f"device x {N_FEATURES} (budget {budget:.4g} of {limit:.4g} B)")
    rows_dev, progs, t_compile = size_rows(rows_dev, mesh, budget)
    rows = rows_dev * args.chips
    if rows != MNIST8M_ROWS * args.chips // 4:
        log(f"# REDUCED: X is {rows} x {N_FEATURES} (from "
            f"{MNIST8M_ROWS * args.chips // 4}): the full-size step does not "
            f"fit one chip's memory")

    sharding = (SingleDeviceSharding(devs[0]) if mesh is None
                else NamedSharding(mesh, P("data", None)))
    t0 = time.perf_counter()
    X, Y, y = make_data(args.seed, rows, sharding)
    log(f"# data: X {X.shape} f32 ({X.nbytes / 1e9:.3f} GB), Y {Y.shape}, "
        f"y {y.shape}, seed {args.seed}, {time.perf_counter() - t0:.3f} s; "
        f"X sharding {X.sharding}")

    log("# kernels (pallas=tpu programs):")
    check_kernels(progs, mesh)

    timings: dict = {}
    arms = ([("tpu", mesh)] if mesh is not None
            else [("tpu", None), ("never", None)])
    run_fits(X, Y, y, arms, timings)
    del X, Y, y

    if mesh is None:
        run_serving(args.seed)

    for d in devs[:args.chips]:
        st = d.memory_stats() or {}
        log(f"# memory {d}: peak {st.get('peak_bytes_in_use')} B, in use "
            f"{st.get('bytes_in_use')} B, limit {st.get('bytes_limit')} B")
    log(f"# time: ahead-of-time compiles {t_compile:.3f} s; fits first/"
        f"second run {json.dumps(timings)}; total "
        f"{time.perf_counter() - t_start:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
