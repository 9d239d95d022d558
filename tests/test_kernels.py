"""Per-kernel validation: Pallas skeletons (interpret mode) vs the ref.py
pure-jnp oracle, swept over shapes, dtypes, variants and programs."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ir
from repro.core.cplan import build_cplan
from repro.core.select import plan
from repro.kernels import ref
from repro.kernels.blocksparse import BCSR, DictCompressed, pad_to_blocks
from repro.kernels.cellwise import cell_pallas
from repro.kernels.multiagg import multiagg_pallas
from repro.kernels.outerprod import outer_pallas
from repro.kernels.rowwise import row_pallas

rng = np.random.default_rng(3)


def _fused_cplan(build_expr, bindings, mode="gen", want=None):
    """Plan the expression and return (cplan, env) of the fused operator.
    ``want`` forces a template type at the output root (kernel sweeps test
    a specific skeleton regardless of what the cost model would pick)."""
    exprs = {k: ir.matrix(k, v.shape if not isinstance(v, BCSR) else v.shape,
                          sparsity=(v.block_sparsity if isinstance(v, BCSR)
                                    else 1.0))
             for k, v in bindings.items()}
    outs = build_expr(**exprs)
    g = ir.Graph.build([outs] if not isinstance(outs, (tuple, list))
                       else list(outs))
    if want is not None:
        from repro.core.cost import _build_spec
        from repro.core.explore import explore
        memo = explore(g)
        root = g.outputs[0]
        entry = next(e for e in memo.entries(root.nid)
                     if e.ttype == want and e.can_root)
        spec = _build_spec(g, memo, root.nid, entry, set())
    else:
        p = plan(g, mode)
        fused = [s for s in p.specs if getattr(s, "fused", False)]
        assert fused, "expression did not produce a fused operator"
        spec = fused[-1]
    cp = build_cplan(g, spec)
    name_by_nid = {n.nid: n.name for n in g.inputs()}
    env = {b.nid: bindings[name_by_nid[b.nid]] for b in cp.binds}
    return cp, env


def _dense_env(env):
    return {k: (v.todense() if hasattr(v, "todense") else v)
            for k, v in env.items()}


SHAPES = [(8, 8), (16, 128), (33, 7), (128, 256), (256, 96)]
DTYPES = [jnp.float32]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("variant", ["full", "row", "col", "none"])
def test_cell_kernel_sweep(shape, dtype, variant):
    X = jnp.asarray(rng.normal(size=shape), dtype)
    Y = jnp.asarray(rng.normal(size=shape), dtype)
    v = jnp.asarray(rng.normal(size=(shape[0], 1)), dtype)

    def expr(X, Y, v):
        c = ir.abs_(X) * Y + v * 2.0
        return {"full": c.sum(), "row": c.rowsums(),
                "col": c.colsums(), "none": c}[variant]

    cp, env = _fused_cplan(expr, dict(X=X, Y=Y, v=v))
    got = cell_pallas(cp, env, interpret=True)
    exp = ref.execute_dense(cp, _dense_env(env))
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("shape", [(16, 16), (64, 48), (128, 128)])
@pytest.mark.parametrize("aggs", [("sum", "sum"), ("sum", "max"),
                                  ("min", "max", "sum")])
def test_multiagg_kernel_sweep(shape, aggs):
    X = jnp.asarray(rng.normal(size=shape), jnp.float32)
    Y = jnp.asarray(rng.normal(size=shape), jnp.float32)

    def expr(X, Y):
        outs = []
        chains = [X * Y, X ** 2, ir.abs_(Y)]
        for a, c in zip(aggs, chains):
            outs.append({"sum": c.sum(), "min": c.min_(),
                         "max": c.max_()}[a])
        return tuple(outs)

    cp, env = _fused_cplan(expr, dict(X=X, Y=Y))
    if not cp.extra:
        pytest.skip("planner did not combine (single agg)")
    got = multiagg_pallas(cp, env, interpret=True)
    exp = ref.execute_dense(cp, _dense_env(env))
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("m", [32, 100, 256])
@pytest.mark.parametrize("k", [1, 4, 16])
def test_row_kernel_mmchain_sweep(m, k):
    X = jnp.asarray(rng.normal(size=(m, 24)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(24, k)), jnp.float32)

    def expr(X, v):
        return X.T @ (X @ v)

    cp, env = _fused_cplan(expr, dict(X=X, v=v))
    got = row_pallas(cp, env, interpret=True)
    exp = ref.execute_dense(cp, _dense_env(env))
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                               rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("variant", ["rowsum_chain", "full", "noagg"])
def test_row_kernel_variants(variant):
    X = jnp.asarray(rng.normal(size=(64, 20)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(20, 3)), jnp.float32)

    def expr(X, v):
        q = (X @ v)
        if variant == "rowsum_chain":
            return (q * 2.0).rowsums()
        if variant == "full":
            return (q ** 2).sum()
        return q * q.rowsums()

    cp, env = _fused_cplan(expr, dict(X=X, v=v))
    got = row_pallas(cp, env, interpret=True)
    exp = ref.execute_dense(cp, _dense_env(env))
    np.testing.assert_allclose(np.asarray(got).reshape(np.asarray(exp).shape),
                               np.asarray(exp), rtol=1e-3, atol=1e-3)


def _random_bcsr(mb, nb, bs, density, rng):
    mask = rng.random((mb, nb)) < density
    mask.flat[0] = True
    dense = rng.normal(size=(mb * bs, nb * bs)).astype(np.float32)
    dense *= np.kron(mask, np.ones((bs, bs), np.float32))
    return BCSR.from_dense(dense, bs=bs), jnp.asarray(dense)


@pytest.mark.parametrize("bs", [128])
@pytest.mark.parametrize("grid", [(2, 2), (4, 3)])
# 0.0 = empty grid except the one forced block (empty-block parity)
@pytest.mark.parametrize("density", [0.0, 0.3, 0.7, 1.0])
@pytest.mark.parametrize("variant", ["right_mm", "full"])
def test_outer_kernel_sweep(bs, grid, density, variant):
    Xs, Xd = _random_bcsr(grid[0], grid[1], bs, density, rng)
    m, n = Xs.shape
    U = jnp.asarray(rng.normal(size=(m, 8)), jnp.float32)
    V = jnp.asarray(rng.normal(size=(n, 8)), jnp.float32)

    def expr(X, U, V):
        c = ir.neq0(X) * (U @ V.T)
        return c @ V if variant == "right_mm" else c.sum()

    from repro.core.templates import TType
    cp, env = _fused_cplan(expr, dict(X=Xs, U=U, V=V), want=TType.OUTER)
    got = outer_pallas(cp, env, interpret=True)
    dense_env = {k: (Xd if isinstance(v, BCSR) else v)
                 for k, v in env.items()}
    exp = ref.execute_dense(cp, dense_env)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                               rtol=1e-3, atol=1e-3)


def test_bcsr_roundtrip():
    Xs, Xd = _random_bcsr(3, 4, 128, 0.4, rng)
    np.testing.assert_array_equal(np.asarray(Xs.todense()), np.asarray(Xd))
    Xt = Xs.T
    np.testing.assert_array_equal(np.asarray(Xt.todense()),
                                  np.asarray(Xd).T)
    # transposed copy stays row-major sorted
    rows = np.asarray(Xt.rows)
    assert all(rows[i] <= rows[i + 1] for i in range(len(rows) - 1))


def test_dict_compressed_roundtrip():
    x = np.round(rng.normal(size=(500, 6)) * 3).astype(np.float32)
    c = DictCompressed.from_dense(x)
    np.testing.assert_array_equal(np.asarray(c.todense()), x)
    assert c.compression_ratio > 1.0


def test_pad_to_blocks():
    x = jnp.ones((130, 200))
    p = pad_to_blocks(x, 128)
    assert p.shape == (256, 256)
    assert float(jnp.sum(p)) == 130 * 200


# ---------------------------------------------------------------------------
# template-parity harness: every Pallas skeleton (interpret mode) vs the
# ref.py oracle on dense, sparse (BCSR), and empty-block inputs
# ---------------------------------------------------------------------------

PARITY_KINDS = ["dense", "sparse", "empty"]
_BS = 128


def _parity_matrix(kind, mb=2, nb=3):
    """(bind value, dense mirror): dense array, BCSR at 40% block
    density, or a BCSR whose grid is empty except one forced block."""
    if kind == "dense":
        d = jnp.asarray(rng.normal(size=(mb * _BS, nb * _BS)), jnp.float32)
        return d, d
    density = 0.4 if kind == "sparse" else 0.0
    return _random_bcsr(mb, nb, _BS, density, rng)


@pytest.mark.parametrize("kind", PARITY_KINDS)
@pytest.mark.parametrize("variant", ["none", "row", "col", "full"])
def test_cell_parity_kinds(kind, variant):
    X, Xd = _parity_matrix(kind)
    Y = jnp.asarray(rng.normal(size=Xd.shape), jnp.float32)

    def expr(X, Y):
        c = ir.abs_(X) * Y + 0.5
        return {"none": c, "row": c.rowsums(), "col": c.colsums(),
                "full": c.sum()}[variant]

    cp, env = _fused_cplan(expr, dict(X=X, Y=Y))
    got = cell_pallas(cp, _dense_env(env), interpret=True)
    exp = ref.execute_dense(cp, _dense_env(env))
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("kind", PARITY_KINDS)
def test_row_parity_kinds(kind):
    X, Xd = _parity_matrix(kind)
    v = jnp.asarray(rng.normal(size=(Xd.shape[1], 4)), jnp.float32)

    def expr(X, v):
        return X.T @ (X @ v)

    cp, env = _fused_cplan(expr, dict(X=X, v=v))
    got = row_pallas(cp, _dense_env(env), interpret=True)
    exp = ref.execute_dense(cp, _dense_env(env))
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                               rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("kind", PARITY_KINDS)
def test_multiagg_parity_kinds(kind):
    X, Xd = _parity_matrix(kind)
    Y = jnp.asarray(rng.normal(size=Xd.shape), jnp.float32)

    def expr(X, Y):
        return (X * Y).sum(), (X ** 2).sum(), ir.abs_(Y).max_()

    cp, env = _fused_cplan(expr, dict(X=X, Y=Y))
    if not cp.extra:
        pytest.skip("planner did not combine (single agg)")
    got = multiagg_pallas(cp, _dense_env(env), interpret=True)
    exp = ref.execute_dense(cp, _dense_env(env))
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("kind", ["sparse", "empty"])
def test_bcsr_exploit_path_parity(kind):
    """The sparsity-exploiting jnp execution path (ops.execute on a BCSR
    driver) must agree with the dense oracle — including grids with
    entirely empty block-rows."""
    from repro.kernels.ops import execute
    X, Xd = _parity_matrix(kind)
    Y = jnp.asarray(rng.normal(size=Xd.shape), jnp.float32)

    def expr(X, Y):
        return (ir.abs_(X) * Y).sum()          # sparse-safe wrt X

    cp, env = _fused_cplan(expr, dict(X=X, Y=Y))
    got = execute(cp, env)
    exp = ref.execute_dense(cp, _dense_env(env))
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dim,target,quantum,want", [
    (784, 512, 128, 784),            # no 128-multiple divides 784: full width
    (2_025_000, 256, 8, 216),        # 2^3·3^4·5^5: largest 8-multiple ≤ 256
    (2_025_000, 128, 8, 120),
    (65_536, 256, 8, 256),
    (200, 256, 8, 200),              # fits the target: one full block
    (1_000_003, 256, 8, 1_000_003),  # prime: only the whole axis is legal
])
def test_pick_block_is_tile_aligned(dim, target, quantum, want):
    """Blocks are (8,128)-aligned divisors or the whole axis — the only
    shapes Mosaic lowers."""
    from repro.kernels.cellwise import pick_block
    b = pick_block(dim, target, quantum)
    assert b == want
    assert dim % b == 0 and (b == dim or b % quantum == 0)


def test_kernel_fallback_names_its_reason():
    """A dense operator whose only legal block overflows VMEM (a prime
    row count) takes its XLA body with a stated reason; an aligned one
    takes its kernel; a dense Outer main says why it has none."""
    from repro.kernels.ops import kernel_fallback

    def expr(X, y):
        return (ir.relu(X - 1.0) * y).sum()

    for m, ok in ((2_025_000, True), (1_000_003, False)):
        exprs = {"X": ir.matrix("X", (m, 784)), "y": ir.matrix("y", (m, 1))}
        g = ir.Graph.build([expr(**exprs)])
        ep = plan(g, "gen")
        spec = next(s for s in ep.specs if getattr(s, "fused", True))
        cp = build_cplan(g, spec)
        reason = kernel_fallback(cp, {b.nid: b.shape for b in cp.binds})
        assert (reason is None) == ok, reason
        if not ok:
            assert "VMEM" in reason and "(1000003, 784)" in reason
