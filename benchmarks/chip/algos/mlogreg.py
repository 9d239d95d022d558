"""The MLogReg configuration's data, its calls into the program (the fit
and the scoring region) and its reference.  Data as the L2SVM
configuration's, with Y one-hot over the classes."""

from __future__ import annotations

from chipbench.seeding import seed_key


def make_data(seed: int, cfg: dict, device) -> dict:
    """X (rows, features) and Y (rows, classes) one-hot, f32, made on
    ``device`` from ``seed`` in one jitted call."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    m, n, k = cfg["rows"], cfg["features"], cfg["classes"]

    def gen(key):
        kx, kw, ke = jax.random.split(key, 3)
        X = jax.random.normal(kx, (m, n), jnp.float32)
        W = jax.random.normal(kw, (n, k), jnp.float32) / jnp.sqrt(n)
        logits = X @ W + 0.5 * jax.random.normal(ke, (m, k), jnp.float32)
        Y = jax.nn.one_hot(jnp.argmax(logits, axis=1), k, dtype=jnp.float32)
        return X, Y

    one = SingleDeviceSharding(device)
    X, Y = jax.jit(gen, out_shardings=(one, one))(seed_key(seed))
    return {"X": jax.block_until_ready(X), "Y": jax.block_until_ready(Y)}


def fit(data: dict, cfg: dict):
    """One whole fit through the program's entry point: (B, objectives)."""
    from repro.algos import mlogreg
    return mlogreg.run(data["X"], data["Y"], lam=cfg["lam"],
                       max_outer=cfg["max_outer"], max_inner=cfg["max_inner"],
                       eps=cfg["eps"], mode=cfg["mode"], pallas=cfg["pallas"])


def reference(data: dict, cfg: dict, refs, dtype: str = "float32"):
    return refs.fit(data["X"], data["Y"], cfg["lam"], cfg["max_outer"],
                    cfg["max_inner"], dtype=dtype)


def score_region():
    """The fused region a scoring request runs: softmax(X @ B)."""
    from repro.algos import mlogreg
    return mlogreg._probs


def regions(cfg: dict) -> list:
    """(label, fused region, operand shapes, differentiated?) of every
    staged program a fit runs."""
    from repro.algos import mlogreg
    m, n, k = cfg["rows"], cfg["features"], cfg["classes"]
    return [("mlogreg.probs", mlogreg._probs, ((m, n), (n, k)), False),
            ("mlogreg.objective", mlogreg._nll_obj_reg,
             ((m, n), (n, k), (m, k), (1, 1)), True),
            ("mlogreg.hvp", mlogreg._hvp, ((m, n), (n, k), (m, k)), False)]


def objective(data: dict, cfg: dict, B):
    """The program's fused objective at ``B`` (for planted faults)."""
    import jax.numpy as jnp
    from repro.algos import mlogreg
    from repro.core import FusionContext
    lam = jnp.full((1, 1), cfg["lam"], jnp.float32)
    with FusionContext(mode=cfg["mode"], pallas=cfg["pallas"]):
        return float(mlogreg._nll_obj_reg(data["X"], B, data["Y"],
                                          lam)[0, 0])
