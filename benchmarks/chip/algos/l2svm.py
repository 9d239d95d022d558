"""The L2SVM configuration's data, its call into the program and its
reference.  Data are those of the program's bring-up check: X ~ N(0, 1)
with the configuration's width, labels from a random linear model over
the configuration's classes plus noise, y = +1 for class 0, else -1."""

from __future__ import annotations

from chipbench.seeding import seed_key


def make_data(seed: int, cfg: dict, device) -> dict:
    """X (rows, features) and y (rows, 1), f32, made on ``device`` from
    ``seed`` in one jitted call."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    m, n, k = cfg["rows"], cfg["features"], cfg["classes"]

    def gen(key):
        kx, kw, ke = jax.random.split(key, 3)
        X = jax.random.normal(kx, (m, n), jnp.float32)
        W = jax.random.normal(kw, (n, k), jnp.float32) / jnp.sqrt(n)
        logits = X @ W + 0.5 * jax.random.normal(ke, (m, k), jnp.float32)
        cls = jnp.argmax(logits, axis=1)
        y = jnp.where(cls == 0, 1.0, -1.0).astype(jnp.float32)[:, None]
        return X, y

    one = SingleDeviceSharding(device)
    X, y = jax.jit(gen, out_shardings=(one, one))(seed_key(seed))
    return {"X": jax.block_until_ready(X), "y": jax.block_until_ready(y)}


def fit(data: dict, cfg: dict):
    """One whole fit through the program's entry point: (w, objectives)."""
    from repro.algos import l2svm
    return l2svm.run(data["X"], data["y"], lam=cfg["lam"],
                     max_iter=cfg["max_iter"], eps=cfg["eps"],
                     mode=cfg["mode"], pallas=cfg["pallas"])


def reference(data: dict, cfg: dict, refs, dtype: str = "float32"):
    return refs.fit(data["X"], data["y"], cfg["lam"], cfg["max_iter"],
                    dtype=dtype)


def regions(cfg: dict) -> list:
    """(label, fused region, operand shapes, differentiated?) of every
    staged program a fit runs."""
    from repro.algos import l2svm
    m, n = cfg["rows"], cfg["features"]
    return [("l2svm.objective", l2svm._objective_full,
             ((m, n), (n, 1), (m, 1), (1, 1)), True),
            ("l2svm.hinge", l2svm._hinge, ((m, n), (n, 1), (m, 1)), False),
            ("l2svm.search_terms", l2svm._search_terms,
             ((m, 1), (m, 1)), False)]


def objective(data: dict, cfg: dict, w):
    """The program's fused objective at ``w`` (for planted faults)."""
    import jax.numpy as jnp
    from repro.algos import l2svm
    from repro.core import FusionContext
    lam = jnp.full((1, 1), cfg["lam"], jnp.float32)
    with FusionContext(mode=cfg["mode"], pallas=cfg["pallas"]):
        return float(l2svm._objective_full(data["X"], w, data["y"],
                                           lam)[0, 0])
