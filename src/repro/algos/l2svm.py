"""L2-regularized squared-hinge SVM (2 classes) — SystemML `l2-svm.dml`.

Outer conjugate-direction iterations with an exact inner Newton line
search.  Fusion sites: the hinge chain relu(1 − y⊙(Xw)) (Cell), the
line-search and objective multi-aggregates (MAgg), and Xᵀ(out⊙y) (Row).

The gradient is ``jax.grad`` of the fused objective: the backward pass is
planned through explore → select, so ∇obj executes the same generated
Row-template operator the hand-derived ``_grad`` expression pins in
``tests/golden/plans.json`` (the parity harness keeps both in lockstep).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .util import fs
from repro.core import ir, fused, FusionContext

# fused regions ---------------------------------------------------------------

@fused
def _hinge(X, w, y):
    return ir.relu(1.0 - y * (X @ w))


@fused
def _objective_full(X, w, y, lam):
    """0.5·Σ relu(1 − y⊙(Xw))² + 0.5·λ·Σ w² — differentiable fused forward;
    jax.grad of this replaces the hand-written −Xᵀ(out⊙y) + λw."""
    out = ir.relu(1.0 - y * (X @ w))
    return 0.5 * (out ** 2).sum() + 0.5 * lam * (w ** 2).sum()


# hand-derived gradient + split objective: golden-plan pins and the
# jax.grad parity harness (tests/test_staged_api.py) — not used by run().
@fused
def _grad(X, out, y, w, lam):
    return -1.0 * (X.T @ (out * y)) + lam * w


@fused
def _search_terms(out, yXs):
    act = out > 0.0
    return (act * out * yXs).sum(), (act * yXs * yXs).sum()


@fused
def _objective(out, w):
    return (out ** 2).sum(), (w ** 2).sum()


def run(X, y, lam: float = 1e-3, max_iter: int = 20, eps: float = 1e-12,
        mode: str = "gen", pallas: str = "never", layout=None,
        staged: bool = True):
    """Returns (w, objective per iteration).

    ``layout`` (a mesh or ``FusionLayout``) scopes every fused region
    through hybrid local/distributed planning: row-parallel operators over
    X run mesh-wide (psum/row-partitioned epilogues), the small w-space
    aggregates stay local.  ``staged=False`` drops to per-operator
    dispatch (debug path; default is one jitted computation per plan)."""
    if mode == "hand":
        return _run_hand(X, y, lam, max_iter, eps)
    m, n = X.shape
    w = jnp.zeros((n, 1), jnp.float32)
    lam_s = jnp.full((1, 1), lam, jnp.float32)
    objs = []
    with FusionContext(mode=mode, pallas=pallas, layout=layout,
                       staged=staged):
        obj_grad = jax.value_and_grad(
            lambda w_: _objective_full(X, w_, y, lam_s)[0, 0])
        _, g = obj_grad(w)
        s = -g
        for _ in range(max_iter):
            Xs = X @ s                        # basic GEMV
            out = _hinge(X, w, y)
            num_t, den_t = _search_terms(out, y * Xs)
            num = fs(num_t) - lam * fs(jnp.sum(w * s))
            den = fs(den_t) + lam * fs(jnp.sum(s * s))
            step = num / max(den, 1e-30)
            w = w + step * s
            val, g_new = obj_grad(w)          # fused forward + fused backward
            objs.append(fs(val))
            beta = fs(jnp.sum(g_new * g_new)) / max(
                fs(jnp.sum(g * g)), 1e-30)
            s = -g_new + beta * s
            g = g_new
            if fs(jnp.sum(g * g)) < eps:
                break
    return w, objs


def _run_hand(X, y, lam, max_iter, eps):
    """Hand-written jnp baseline (the paper's 'Fused' arm)."""
    m, n = X.shape
    w = jnp.zeros((n, 1), jnp.float32)
    out = jnp.maximum(1.0 - y * (X @ w), 0.0)
    g = -(X.T @ (out * y)) + lam * w
    s = -g
    objs = []
    for _ in range(max_iter):
        Xs = X @ s
        out = jnp.maximum(1.0 - y * (X @ w), 0.0)
        act = (out > 0).astype(jnp.float32)
        yXs = y * Xs
        num = float(jnp.sum(act * out * yXs)) - lam * float(jnp.sum(w * s))
        den = float(jnp.sum(act * yXs * yXs)) + lam * float(jnp.sum(s * s))
        step = num / max(den, 1e-30)
        w = w + step * s
        out = jnp.maximum(1.0 - y * (X @ w), 0.0)
        objs.append(0.5 * float(jnp.sum(out ** 2))
                    + 0.5 * lam * float(jnp.sum(w ** 2)))
        g_new = -(X.T @ (out * y)) + lam * w
        beta = float(jnp.sum(g_new * g_new)) / max(float(jnp.sum(g * g)),
                                                   1e-30)
        s = -g_new + beta * s
        g = g_new
        if float(jnp.sum(g * g)) < eps:
            break
    return w, objs
