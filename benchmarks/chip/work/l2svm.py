"""The work an L2SVM fit requires, fixed by its equations and not by any
plan: each pass reads X (rows x features, f32) once.  A fit is one pass
for the initial gradient, then per iteration one line-search pass (X w
and X s, row-local) and one objective/gradient pass (X w and
X^T(out * y), row-local)."""

from __future__ import annotations


def passes_per_fit(cfg: dict) -> int:
    return 1 + 2 * cfg["max_iter"]


def outer_per_fit(cfg: dict) -> int:
    return cfg["max_iter"]


def bytes_per_fit(cfg: dict) -> float:
    return passes_per_fit(cfg) * cfg["rows"] * cfg["features"] * 4.0


def flops_per_fit(cfg: dict) -> float:
    """Two products over X per pass, 2 FLOPs per element each."""
    return passes_per_fit(cfg) * 4.0 * cfg["rows"] * cfg["features"]
