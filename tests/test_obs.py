"""Program spans and kernel names (``repro.obs``): the span table, the
spans the fit loops and the planner open, and the names every generated
Pallas kernel carries.  The name in the TPU HLO text is checked with the
other described-chip compiles, in ``tests/test_tpu_compile.py``."""

import re
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.algos import data, l2svm, mlogreg
from repro.core import FusionContext, fused, ir
from repro.core.codegen import PLAN_CACHE
from repro.core.cost import FusedOpSpec
from repro.core.select import MultiAggSpec

NAME = re.compile(r"^(cell|row|magg|outer)_([a-z_]+)_([0-9a-f]{8})$")


def _delta(before: dict, name: str) -> tuple[float, int]:
    now = obs.snapshot().get(name, {"seconds": 0.0, "count": 0})
    was = before.get(name, {"seconds": 0.0, "count": 0})
    return now["seconds"] - was["seconds"], now["count"] - was["count"]


def test_span_table_sums_across_threads():
    """More threads than cores, switching every microsecond: no update
    is lost, and every span's seconds reach the table."""
    name, threads, per = "test.obs.threads", 16, 200
    before = obs.snapshot()
    measured = []
    lock = threading.Lock()

    def work():
        mine = 0.0
        for _ in range(per):
            with obs.span(name) as sp:
                pass
            mine += sp.seconds
        with lock:
            measured.append(mine)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        ts = [threading.Thread(target=work) for _ in range(threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in ts)
    seconds, count = _delta(before, name)
    assert count == threads * per
    assert seconds == pytest.approx(sum(measured), rel=1e-9, abs=1e-12)


def test_nested_span_of_one_name_counts_once():
    before = obs.snapshot()
    with obs.span(obs.PLAN) as outer:
        with obs.span(obs.PLAN) as inner:
            with obs.span("test.obs.child"):
                pass
    seconds, count = _delta(before, obs.PLAN)
    assert count == 1
    assert seconds == pytest.approx(outer.seconds)
    assert 0.0 < inner.seconds <= outer.seconds      # still measured
    assert _delta(before, "test.obs.child")[1] == 1


class _NoSpan:
    """``obs.span`` stubbed out: opens nothing, records nothing."""
    seconds = 0.0

    def __init__(self, name):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return None


@pytest.fixture(scope="module")
def cls_data():
    X, Y, ypm = data.classification(320, 16, k=3, seed=7)
    return jnp.asarray(X), jnp.asarray(Y), jnp.asarray(ypm)


def _l2svm(d):
    X, _Y, ypm = d
    return l2svm.run(X, ypm, max_iter=4)


def _mlogreg(d):
    X, Y, _ypm = d
    return mlogreg.run(X, Y, max_outer=2, max_inner=3)


@pytest.mark.parametrize("fit,syncs_per_iter", [
    # num, w·s, den, s·s, objective, g_new·g_new, g·g, the eps test on g·g
    (_l2svm, 8),
    # objective and r·r, then p·Hp and r·r per inner CG step (3 here)
    (_mlogreg, 2 + 2 * 3),
])
def test_fit_syncs_per_iteration_and_bit_equal_without_spans(
        cls_data, monkeypatch, fit, syncs_per_iter):
    before = obs.snapshot()
    w, objs = fit(cls_data)
    _, syncs = _delta(before, obs.SYNC)
    assert syncs == syncs_per_iter * len(objs)
    monkeypatch.setattr(obs, "span", _NoSpan)
    w0, objs0 = fit(cls_data)
    assert np.array_equal(np.asarray(w), np.asarray(w0))
    assert objs == objs0


def test_planning_one_region_opens_each_phase_once():
    region = fused(lambda X, y: (ir.relu(X - 2.0) * y).sum())
    X, y = jnp.ones((136, 24)), jnp.ones((136, 1))
    before = obs.snapshot()
    planned = region.trace(X, y).plan()
    counts = {phase: _delta(before, phase)[1]
              for phase in (obs.PLAN, obs.PLAN_TRACE, obs.PLAN_REWRITE,
                            obs.PLAN_EXPLORE, obs.PLAN_SELECT,
                            obs.PLAN_VERIFY)}
    assert counts == dict.fromkeys(counts, 1)
    # no rewrite variant was planned, whose explore and select would nest
    # in the sweep (explain() costs the other arms, so it comes after)
    assert planned.explain()["rewrite"]["n_variants"] == 0


def _fused_cplans(planned):
    graph = planned.eplan.graph
    return [PLAN_CACHE.get_or_build(graph, s)[1] for s in planned.eplan.specs
            if isinstance(s, MultiAggSpec)
            or (isinstance(s, FusedOpSpec) and s.fused)]


# shapes no other test uses, so each kernel is traced (and named) here
M, N = 232, 40


@pytest.mark.parametrize("template,region,shapes", [
    ("cell", lambda X, y: (ir.relu(X - 1.0) * y).sum(), [(M, N), (M, 1)]),
    ("row", lambda X, v, P: X.T @ (P * (X @ v)), [(M, N), (N, 3), (M, 3)]),
    ("magg", lambda X, y: ((X * y).sum(), (X * X).sum()),
     [(M, N), (M, 1)]),
])
def test_every_kernel_carries_its_name(template, region, shapes):
    f = fused(region)
    args = [jax.random.normal(jax.random.key(i), s)
            for i, s in enumerate(shapes)]
    ctx = FusionContext(pallas="interpret")
    before = obs.kernel_names()
    compiled = f.trace(*args).plan(context=ctx).compile()
    jax.block_until_ready(compiled(*args))
    new = obs.kernel_names() - before
    cplans = _fused_cplans(compiled.planned)
    assert len(new) == len(cplans) >= 1
    assert {NAME.match(n).groups() for n in new} == {
        (template, cp.variant, cp.cache_key()[:8]) for cp in cplans}


@pytest.mark.parametrize("max_inner,eps,cg_steps", [
    (3, 1e-12, 2 * 3),          # every CG step runs
    (3, 1e30, 2 * 1),           # the first step's residual test stops it
])
def test_mlogreg_fit_opens_grad_and_cg_spans(cls_data, max_inner, eps,
                                             cg_steps):
    X, Y, _ypm = cls_data
    before = obs.snapshot()
    _w, objs = mlogreg.run(X, Y, max_outer=2, max_inner=max_inner, eps=eps)
    assert _delta(before, obs.GRAD)[1] == len(objs) == 2
    assert _delta(before, obs.CG)[1] == cg_steps
    # two reads per outer iteration, two per CG step
    assert _delta(before, obs.SYNC)[1] == 2 * len(objs) + 2 * cg_steps


# shapes no other test uses, so each program is built (and named) here
PM, PN, PK = 248, 24, 3


@pytest.mark.parametrize("region,backward,name", [
    ("_probs", False, "plan__softmax_probs_expr"),   # _probs = fused(_softmax_probs_expr)
    ("_hvp", False, "plan__hvp"),
    ("_nll_obj_reg", False, "plan__nll_obj_reg"),
    ("_nll_obj_reg", True, "plan__nll_obj_reg_bwd"),
    ("_nll_obj_reg", ("B",), "plan__nll_obj_reg_bwd"),
])
def test_staged_program_is_named_by_its_region(region, backward, name):
    shapes = {"_probs": [(PM, PN), (PN, PK)],
              "_hvp": [(PM, PN), (PN, PK), (PM, PK)],
              "_nll_obj_reg": [(PM, PN), (PN, PK), (PM, PK), (1, 1)]}[region]
    args = [jax.random.normal(jax.random.key(i), s)
            for i, s in enumerate(shapes)]
    compiled = getattr(mlogreg, region).trace(*args).plan().compile()
    wrt = None if backward is True else backward    # a tuple: only those
    cplan = compiled._get_bwd(wrt)[0] if backward else compiled._cplan
    assert cplan.name == name
    assert name in obs.program_names()
    fn, _raw = cplan.staged_callable()
    ins = [jax.ShapeDtypeStruct(tuple(nd.shape), jnp.float32)
           for nd in cplan.plan.graph.inputs()]
    assert re.search(rf"module @jit_{name}\b", fn.lower(*ins).as_text())


def test_pruned_backward_is_counted():
    """``repro.bwd.pruned`` counts each backward call planned for fewer
    inputs than its region has, and no call that differentiates every
    input; the pruned one still computes the weights' gradient."""
    X = jax.random.normal(jax.random.key(0), (136, 12))
    w = jax.random.normal(jax.random.key(1), (12, 1))
    y = jnp.sign(jax.random.normal(jax.random.key(2), (136, 1)))
    lam = jnp.full((1, 1), 1e-3, jnp.float32)
    loss = lambda *a: l2svm._objective_full(*a)[0, 0]
    before = obs.snapshot()
    g_w = [jax.grad(loss, argnums=1)(X, w, y, lam) for _ in range(2)]
    assert _delta(before, obs.BWD_PRUNED)[1] == 2
    g_all = jax.grad(loss, argnums=(0, 1, 2, 3))(X, w, y, lam)
    assert _delta(before, obs.BWD_PRUNED)[1] == 2
    np.testing.assert_allclose(np.asarray(g_w[1]), np.asarray(g_all[1]),
                               rtol=1e-5, atol=1e-5)
