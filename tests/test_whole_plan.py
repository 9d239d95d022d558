"""Whole-plan staged execution: one jitted computation per ExecPlan.

Parity: the staged path and the per-operator debug path
(``compile(staged=False)``) must agree to 1e-5 across dense plans, BCSR
fallback plans, and hybrid layout plans — forward *and* ``jax.grad``.
Safety: inputs are never donated (re-calling with the same arrays is
valid and the arrays survive).  Caching: structurally-equal plans share
one staged function via the whole-plan cache, layered on the
operator-level plan cache.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from diffharness import assert_staged_parity
from repro.core import (FusionContext, fused, fusion_mode, ir,
                        plan_cache_stats, whole_plan_cache_stats)
from repro.core.codegen import WHOLE_PLAN_CACHE
from repro.dist.planner import LogicalMesh

rng = np.random.default_rng(21)


def arr(*shape):
    return jnp.asarray(rng.normal(size=shape), jnp.float32)


def _close(a, b, tol=1e-5):
    a = a if isinstance(a, tuple) else (a,)
    b = b if isinstance(b, tuple) else (b,)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=tol, atol=tol)


# --------------------------------------------------------------------------
# staged vs per-op parity: forward and jax.grad, per algorithm
# --------------------------------------------------------------------------

def _l2svm_case():
    from repro.algos import l2svm
    X, w = arr(300, 20), arr(20, 1)
    y = jnp.asarray(np.sign(rng.normal(size=(300, 1))), jnp.float32)
    lam = jnp.full((1, 1), 1e-3, jnp.float32)
    return l2svm._objective_full, (X, w, y, lam), 1


def _mlogreg_case():
    from repro.algos import mlogreg
    m, n, k = 200, 12, 4
    X, B = arr(m, n), arr(n, k) * 0.1
    lab = rng.integers(0, k, size=m)
    Y = jnp.asarray(np.eye(k, dtype=np.float32)[lab])
    lam = jnp.full((1, 1), 1e-3, jnp.float32)
    return mlogreg._nll_obj_reg, (X, B, Y, lam), 1


def _autoencoder_case():
    from repro.algos import autoencoder
    Xb = arr(128, 32)
    Ws = [arr(32, 16) * 0.2, arr(16, 2) * 0.2,
          arr(2, 16) * 0.2, arr(16, 32) * 0.2]
    bs = [jnp.zeros((1, d), jnp.float32) for d in (16, 2, 16, 32)]
    args = (Xb, Ws[0], bs[0], Ws[1], bs[1], Ws[2], bs[2], Ws[3], bs[3])
    return autoencoder._recon_loss, args, 1


CASES = {"l2svm": _l2svm_case, "mlogreg": _mlogreg_case,
         "autoencoder": _autoencoder_case}


@pytest.mark.parametrize("name", sorted(CASES))
def test_parity_staged_vs_per_op(name):
    """Forward + grad parity of the staged whole-plan path against the
    per-operator debug path, via the shared differential harness."""
    f, args, gi = CASES[name]()
    assert_staged_parity(f, args, grad_index=gi)


def test_hybrid_layout_parity_staged_vs_per_op():
    """Hybrid plans (abstract 1×8 mesh: distributed placements costed,
    bodies run locally) execute identically on both paths, forward and
    grad."""
    from repro.algos import mlogreg
    f, args, gi = _mlogreg_case()
    mesh = LogicalMesh({"data": 8})
    planned = assert_staged_parity(f, args, grad_index=gi, layout=mesh)
    assert any(o.get("placement") == "distributed"
               for o in planned.explain()["winner"]["operators"])
    # the call-sugar path under a scoped mesh context agrees too
    with FusionContext(mode="gen", layout=mesh):
        g_staged = jax.grad(
            lambda B: mlogreg._nll_obj_reg(args[0], B, args[2],
                                           args[3])[0, 0])(args[1])
    with FusionContext(mode="gen", layout=mesh, staged=False):
        g_per_op = jax.grad(
            lambda B: mlogreg._nll_obj_reg(args[0], B, args[2],
                                           args[3])[0, 0])(args[1])
    _close(g_staged, g_per_op)


def test_bcsr_compiles_staged_and_agrees():
    """Sparse operands compile staged like everything else (the BCSR
    program lowers inside the whole-plan jit) — same numbers as the
    dense reference, one dispatch per call, no recorded fallback."""
    from repro.kernels.blocksparse import BCSR
    rng2 = np.random.default_rng(5)
    mask = np.kron(rng2.random((4, 3)) < 0.5, np.ones((16, 16)))
    mask[:16, :16] = 1.0
    Xd = (rng2.normal(size=(64, 48)) * mask).astype(np.float32)
    X = BCSR.from_dense(Xd, bs=16)
    B = arr(64, 8)
    f = fused(lambda X, B: X.T @ B)
    planned = f.trace(X, B).plan(mode="gen")
    compiled = planned.compile(staged=True)
    got = compiled(X, B)
    _close(got, jnp.asarray(Xd.T) @ B, tol=2e-4)
    assert compiled._cplan._staged      # staged, not per-op
    assert compiled._cplan.fallbacks == []
    assert compiled.explain()["execution"]["fallbacks"] == []


def test_pallas_interpret_compiles_staged():
    """pallas="interpret" stages like any other mode: the interpreted
    Pallas kernels trace inside the whole-plan jit."""
    f = fused(lambda X, Y: (X * Y + 1.0).sum())
    X, Y = arr(32, 32), arr(32, 32)
    planned = f.trace(X, Y).plan(mode="gen")
    compiled = planned.compile(pallas="interpret")
    _close(compiled(X, Y), jnp.sum(X * Y + 1.0).reshape(1, 1), tol=2e-4)
    assert compiled._cplan._staged
    assert compiled._cplan.fallbacks == []


# --------------------------------------------------------------------------
# donation safety
# --------------------------------------------------------------------------

def test_inputs_not_donated_recall_is_valid():
    """The staged jit never donates inputs: calling twice with the same
    arrays is valid, returns identical results, and the input buffers
    survive unchanged."""
    f = fused(lambda X, Y: (ir.sigmoid(X) * Y).rowsums())
    X, Y = arr(64, 16), arr(64, 16)
    x_copy = np.asarray(X).copy()
    compiled = f.trace(X, Y).plan(mode="gen").compile(staged=True)
    out1 = compiled(X, Y)
    out2 = compiled(X, Y)                      # same arrays, second call
    _close(out1, out2, tol=0.0)
    # the inputs are still live, readable, bit-identical buffers
    np.testing.assert_array_equal(np.asarray(X), x_copy)


def test_explain_reports_staged_execution_and_donation():
    f = fused(lambda X, w, y: (ir.relu(1.0 - y * (X @ w)) ** 2).sum())
    planned = f.trace(np.zeros((64, 8), np.float32),
                      np.zeros((8, 1), np.float32),
                      np.zeros((64, 1), np.float32)).plan(mode="gen")
    ex = planned.explain()["execution"]
    assert ex["staged"] is True
    assert ex["dispatches_per_call"] == 1
    assert ex["donated_inputs"] == []
    assert ex["freed_intermediates"] >= 1


# --------------------------------------------------------------------------
# whole-plan cache (layered on the operator-level plan cache)
# --------------------------------------------------------------------------

def test_whole_plan_cache_structural_hit():
    """A structurally-equal plan from a different trace reuses the staged
    function (whole-plan hit) while still counting operator-level cache
    traffic underneath."""
    WHOLE_PLAN_CACHE.clear()
    X, Y = arr(16, 16), arr(16, 16)
    f = fused(lambda X, Y: (X * Y).sum())
    with fusion_mode("gen"):
        f(X, Y)
        st = whole_plan_cache_stats()
        assert st.misses >= 1
        misses_before, hits_before = st.misses, st.hits
        g = fused(lambda A, B: (A * B).sum())   # same structure, new trace
        g(X, Y)
    st = whole_plan_cache_stats()
    assert st.misses == misses_before           # no new staged build
    assert st.hits > hits_before
    assert plan_cache_stats().total > 0         # operator layer still hit


def test_per_op_fallback_signature_distinct():
    """staged and per-op compilations of one @fused wrapper are distinct
    context signatures — no silent cross-contamination."""
    f = fused(lambda X: (X * 2.0).rowsums())
    X = arr(24, 6)
    with fusion_mode("gen"):
        a = f(X)
    with fusion_mode("gen", staged=False):
        b = f(X)
    assert len(f._staged) == 2
    _close(a, b, tol=0.0)


def test_literals_are_trace_constants():
    """Literal (1,1) operands are folded into the staged trace — the
    jaxpr has no per-call literal rebuild (constants appear inline)."""
    f = fused(lambda X: (X * 3.5 + 1.25).sum())
    X = arr(8, 8)
    compiled = f.trace(X).plan(mode="gen").compile(staged=True)
    compiled(X)                                  # build
    _fn, raw = compiled._cplan.staged_callable()
    jaxpr = jax.make_jaxpr(raw)(X)
    # one input var only — the literals are not arguments
    assert len(jaxpr.jaxpr.invars) == 1
