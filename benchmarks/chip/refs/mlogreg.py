"""Plain ``jax.numpy`` multinomial logistic regression: SystemML's
``MultiLogReg.dml`` as the program runs it (trust-region-free Newton-CG
from B = 0, k-class softmax), and the scoring region's probabilities.
It imports nothing of the program.

``dtype="float32"`` runs every matrix product at ``highest`` precision:
the reference.  A lower ``dtype`` keeps the data, the weights and every
intermediate in that type at the default precision: the control.
"""

from __future__ import annotations

import contextlib


def fit(X, Y, lam: float, outer: int, inner: int, dtype: str = "float32"):
    """(weights, regularized objective at the start of each outer
    iteration)."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(dtype)
    lam = float(lam)

    @jax.jit
    def probs_obj_grad(X, Y, B):
        Z = X @ B
        E = jnp.exp(Z - Z.max(axis=1, keepdims=True))
        P = (E / E.sum(axis=1, keepdims=True)).astype(dt)
        obj = -jnp.sum(Y * jnp.log(P + 1e-30)) + 0.5 * lam * jnp.sum(B * B)
        return P, obj, (X.T @ (P - Y) + lam * B).astype(dt)

    @jax.jit
    def hvp(X, P, p):
        Q = P * (X @ p)
        return (X.T @ (Q - P * Q.sum(axis=1, keepdims=True))
                + lam * p).astype(dt)

    def dot(a, b):
        return float(jnp.sum(a.astype(jnp.float32) * b.astype(jnp.float32)))

    prec = (jax.default_matmul_precision("highest") if dt == jnp.float32
            else contextlib.nullcontext())
    with prec:
        X, Y = X.astype(dt), Y.astype(dt)
        B = jnp.zeros((X.shape[1], Y.shape[1]), dt)
        objs = []
        for _ in range(outer):
            P, obj, G = probs_obj_grad(X, Y, B)
            objs.append(float(obj))
            d, r = jnp.zeros_like(B), -G
            p, rs = r, dot(r, r)
            for _ in range(inner):
                Hp = hvp(X, P, p)
                alpha = rs / max(dot(p, Hp), 1e-30)
                d, r = (d + alpha * p).astype(dt), (r - alpha * Hp).astype(dt)
                rs_new = dot(r, r)
                if rs_new < 1e-12:
                    break
                p, rs = (r + (rs_new / rs) * p).astype(dt), rs_new
            B = (B + d).astype(dt)
    return B, objs


def probs(X, B):
    """Softmax(X @ B) row by row, in float64 on the host (the scoring
    reference)."""
    import numpy as np
    Z = np.asarray(X, np.float64) @ np.asarray(B, np.float64)
    E = np.exp(Z - Z.max(axis=1, keepdims=True))
    return E / E.sum(axis=1, keepdims=True)


def probs_lowp(X, B, dtype: str = "bfloat16"):
    """The same probabilities computed in ``dtype`` on the device (the
    scoring control)."""
    import jax.numpy as jnp
    import numpy as np
    dt = jnp.dtype(dtype)
    Z = jnp.asarray(X, dt) @ jnp.asarray(B, dt)
    E = jnp.exp(Z - Z.max(axis=1, keepdims=True))
    return np.asarray((E / E.sum(axis=1, keepdims=True)).astype(dt),
                      np.float32)
