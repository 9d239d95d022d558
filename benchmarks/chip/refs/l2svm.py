"""Plain ``jax.numpy`` L2SVM: SystemML's ``l2-svm.dml`` as the program
runs it (conjugate directions with an exact Newton line search, from
w = 0).  It imports nothing of the program.

``dtype="float32"`` runs every matrix product at ``highest`` precision:
the reference.  A lower ``dtype`` keeps the data, the weights and every
intermediate in that type at the default precision: the control, which a
sound comparison has to refuse.
"""

from __future__ import annotations

import contextlib


def fit(X, y, lam: float, iters: int, dtype: str = "float32"):
    """(weights, objective after each iteration)."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(dtype)
    lam = float(lam)

    # the data are arguments of every jitted step: a jitted function
    # that closed over X would embed it in the program as a constant
    @jax.jit
    def grad_obj(X, y, w):
        out = jnp.maximum(1.0 - y * (X @ w), 0.0).astype(dt)
        obj = 0.5 * jnp.sum(out ** 2) + 0.5 * lam * jnp.sum(w ** 2)
        return obj, (-(X.T @ (out * y)) + lam * w).astype(dt)

    @jax.jit
    def line_terms(X, y, w, s):
        Xs = X @ s
        out = jnp.maximum(1.0 - y * (X @ w), 0.0).astype(dt)
        act = (out > 0).astype(dt)
        yXs = y * Xs
        num = jnp.sum(act * out * yXs) - lam * jnp.sum(w * s)
        den = jnp.sum(act * yXs * yXs) + lam * jnp.sum(s * s)
        return num, den

    prec = (jax.default_matmul_precision("highest") if dt == jnp.float32
            else contextlib.nullcontext())
    with prec:
        X, y = X.astype(dt), y.astype(dt)
        w = jnp.zeros((X.shape[1], 1), dt)
        _, g = grad_obj(X, y, w)
        s, objs = -g, []
        for _ in range(iters):
            num, den = line_terms(X, y, w, s)
            w = (w + float(num) / max(float(den), 1e-30) * s).astype(dt)
            obj, g_new = grad_obj(X, y, w)
            objs.append(float(obj))
            beta = float(jnp.sum(g_new.astype(jnp.float32) ** 2)) / max(
                float(jnp.sum(g.astype(jnp.float32) ** 2)), 1e-30)
            s, g = (-g_new + beta * s).astype(dt), g_new
    return w, objs
