"""The work an MLogReg fit requires, fixed by its equations and not by
any plan: each pass reads X (rows x features, f32) once.  Each outer
iteration is one probabilities/objective/gradient pass (X B and
X^T(P - Y), row-local) and one pass per CG step (X p and
X^T(Q - P * rowSums(Q)), row-local), each over the classes' columns."""

from __future__ import annotations


def passes_per_fit(cfg: dict) -> int:
    return cfg["max_outer"] * (1 + cfg["max_inner"])


def outer_per_fit(cfg: dict) -> int:
    return cfg["max_outer"]


def bytes_per_fit(cfg: dict) -> float:
    return passes_per_fit(cfg) * cfg["rows"] * cfg["features"] * 4.0


def flops_per_fit(cfg: dict) -> float:
    """Two products of X with k columns per pass, 2 FLOPs per element
    and column each."""
    return (passes_per_fit(cfg) * 4.0 * cfg["rows"] * cfg["features"]
            * cfg["classes"])
