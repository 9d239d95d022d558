"""Multinomial logistic regression via trust-region Newton-CG — SystemML
`MultiLogReg.dml`.

The Hessian-vector product is the paper's Expression (2):

    Q = P[,1:k] ⊙ (X v)
    H = Xᵀ (Q − P[,1:k] ⊙ rowSums(Q))     — one Row-template pass over X.

Fusion sites: softmax probabilities (Row), the HVP (Row col_t_agg), the
gradient Xᵀ(P−Y) (Row), and the log-likelihood aggregate.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .util import fs
from repro import obs
from repro.core import ir, fused, FusionContext


def _softmax_probs_expr(X, B):
    """P (m,k) from logits X@B with an implicit 0-logit baseline class is
    omitted — we use full k-class softmax (Icpt=0, paper config)."""
    Z = X @ B
    m = Z.rowmaxs()
    E = ir.exp(Z - m)
    return E / E.rowsums()


_probs = fused(_softmax_probs_expr)


@fused
def _nll_obj(X, B, Y):
    """−Σ Y⊙log P — differentiable fused forward; jax.grad of this w.r.t.
    B replaces the hand-written Xᵀ(P−Y) (the backward pass is planned, and
    the rowmaxs subgradient cancels by softmax shift-invariance)."""
    Z = X @ B
    m = Z.rowmaxs()
    E = ir.exp(Z - m)
    P = E / E.rowsums()
    return 0.0 - (Y * ir.log(P + 1e-30)).sum()


@fused
def _nll_obj_reg(X, B, Y, lam):
    """−Σ Y⊙log P + 0.5·λ·Σ B² — the full regularized objective as one
    fused region.  Its HOP DAG has two plan partitions with different
    natural placements: the X-row-parallel softmax/NLL chain (mesh-wide
    under a layout, psum epilogue) and the tiny B-space regularizer
    multi-aggregate (local) — the canonical hybrid plan."""
    Z = X @ B
    m = Z.rowmaxs()
    E = ir.exp(Z - m)
    P = E / E.rowsums()
    return (0.0 - (Y * ir.log(P + 1e-30)).sum()
            + 0.5 * lam * (B ** 2).sum())


@fused
def _hvp(X, v, P):
    Q = P * (X @ v)
    return X.T @ (Q - P * Q.rowsums())


# hand-derived gradient + NLL aggregate: golden-plan pins and the jax.grad
# parity harness — run() differentiates the regularized _nll_obj_reg.
@fused
def _grad(X, P, Y):
    return X.T @ (P - Y)


@fused
def _nll_terms(P, Y):
    return (Y * ir.log(P + 1e-30)).sum()


# the fit sufficient statistic ⟨XᵀY, B⟩ = Σ B⊙(XᵀY), written in its
# textbook form.  As written the planner needs two operators (the (n,k)
# XᵀY product, then the weighted aggregate); the SPORES rotation
# sum(B⊙(XᵀY)) = sum((X@B)⊙Y) is a single Row-template pass over X with
# no (n,k) intermediate — the rewrite sweep's demonstrable win, pinned by
# tests/golden/explain_rewrite_mlogreg.json.
@fused
def _fit_terms(X, B, Y):
    return (B * (X.T @ Y)).sum()


def run(X, Y, lam: float = 1e-3, max_outer: int = 10, max_inner: int = 20,
        eps: float = 1e-12, mode: str = "gen", pallas: str = "never",
        layout=None, staged: bool = True):
    """Returns (B, regularized objective per outer iteration).

    ``layout`` (a mesh or ``FusionLayout``) plans every fused region
    hybrid local/distributed — see :func:`_nll_obj_reg`.
    ``staged=False`` drops to per-operator dispatch (debug path)."""
    if mode == "hand":
        return _run_hand(X, Y, lam, max_outer, max_inner, eps)
    m, n = X.shape
    k = Y.shape[1]
    B = jnp.zeros((n, k), jnp.float32)
    lam_s = jnp.full((1, 1), lam, jnp.float32)
    nlls = []
    with FusionContext(mode=mode, pallas=pallas, layout=layout,
                       staged=staged):
        obj_grad = jax.value_and_grad(
            lambda B_: _nll_obj_reg(X, B_, Y, lam_s)[0, 0])
        for _ in range(max_outer):
            P = _probs(X, B)
            with obs.span(obs.GRAD):      # fused forward + fused backward
                val, G = obj_grad(B)
            nlls.append(fs(val))          # == NLL + 0.5·λ‖B‖² as before
            # CG solve (H + lam I) d = -G with fused HVPs
            d = jnp.zeros_like(B)
            r = -G
            p = r
            rs = fs(jnp.sum(r * r))
            for _ in range(max_inner):
                with obs.span(obs.CG):
                    Hp = _hvp(X, p, P) + lam * p
                    alpha = rs / max(fs(jnp.sum(p * Hp)), 1e-30)
                    d = d + alpha * p
                    r = r - alpha * Hp
                    rs_new = fs(jnp.sum(r * r))
                    if rs_new < eps:
                        break
                    p = r + (rs_new / rs) * p
                    rs = rs_new
            B = B + d
    return B, nlls


def _run_hand(X, Y, lam, max_outer, max_inner, eps):
    m, n = X.shape
    k = Y.shape[1]
    B = jnp.zeros((n, k), jnp.float32)
    nlls = []

    def probs(B):
        Z = X @ B
        Z = Z - Z.max(axis=1, keepdims=True)
        E = jnp.exp(Z)
        return E / E.sum(axis=1, keepdims=True)

    for _ in range(max_outer):
        P = probs(B)
        nll = -float(jnp.sum(Y * jnp.log(P + 1e-30))) \
            + 0.5 * lam * float(jnp.sum(B * B))
        nlls.append(nll)
        G = X.T @ (P - Y) + lam * B
        d = jnp.zeros_like(B)
        r = -G
        p = r
        rs = float(jnp.sum(r * r))
        for _ in range(max_inner):
            Q = P * (X @ p)
            Hp = X.T @ (Q - P * Q.sum(axis=1, keepdims=True)) + lam * p
            alpha = rs / max(float(jnp.sum(p * Hp)), 1e-30)
            d = d + alpha * p
            r = r - alpha * Hp
            rs_new = float(jnp.sum(r * r))
            if rs_new < eps:
                break
            p = r + (rs_new / rs) * p
            rs = rs_new
        B = B + d
    return B, nlls
