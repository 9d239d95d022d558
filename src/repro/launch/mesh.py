"""Production mesh construction.

Functions, not module-level constants — importing this module never
touches jax device state (the dry-run sets XLA_FLAGS before any jax use).
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    import numpy as np
    n = int(np.prod(shape))
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=jax.devices()[:n])


def make_host_mesh(model: int = 1):
    """Small mesh over whatever devices exist (tests / local runs)."""
    n = len(jax.devices())
    data = n // model
    return jax.make_mesh((data, model), ("data", "model"))


def fsdp_axes(mesh) -> tuple[str, ...]:
    """Axes used for fully-sharded parameter storage (everything except
    the tensor-parallel axis) — single source of truth in repro.dist."""
    from repro.dist import sharding
    return sharding.fsdp_axes(mesh)


def dp_size(mesh) -> int:
    out = 1
    for a in fsdp_axes(mesh):
        out *= mesh.shape[a]
    return out
