"""The bytes one pass of an MLogReg fit requires, fixed by its equations
and not by any plan (f32, 4 bytes a value).

* A Hessian-vector product H = Xᵀ(Q − P ⊙ rowSums(Q)), Q = P ⊙ (X v),
  reads X and P once (v and H, features x classes, are left out).
* A probabilities/objective/gradient pass, P = softmax(X B), the
  objective −Σ Y ⊙ log P and Xᵀ(P − Y), reads X and Y once and writes P
  once.
"""

from __future__ import annotations


def hvp_bytes(cfg: dict) -> float:
    return 4.0 * cfg["rows"] * (cfg["features"] + cfg["classes"])


def grad_bytes(cfg: dict) -> float:
    return 4.0 * cfg["rows"] * (cfg["features"] + 2 * cfg["classes"])
