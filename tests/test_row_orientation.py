"""The choice of a Row kernel's orientation: lane-major where the staged
plan is called with a main the device stores column-major, row-major
everywhere else.  The CPU stores arrays row-major unless told otherwise,
so each test lays its column-major operands out itself; every test uses
row counts of its own, so its kernels are lowered, and named, here."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Format, Layout
from jax.sharding import SingleDeviceSharding

from repro import obs
from repro.core import FusionContext, fused, ir
from repro.core.codegen import WHOLE_PLAN_CACHE, device_layout

rng = np.random.default_rng(5)
N = 24


def _dev():
    return SingleDeviceSharding(jax.devices()[0])


def _col(a):
    """``a`` on the device, stored column-major."""
    return jax.device_put(a, Format(Layout(major_to_minor=(1, 0)), _dev()))


def _operands(m):
    X = jnp.asarray(rng.normal(size=(m, N)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(N, 1)), jnp.float32)
    y = jnp.asarray(np.sign(rng.normal(size=(m, 1))), jnp.float32)
    return X, w, y


def _hinge():
    return fused(lambda X, w, y: ir.relu(1.0 - y * (X @ w)))


def _compiled(m):
    X, w, y = _operands(m)
    planned = _hinge().trace(X, w, y).plan(
        context=FusionContext(pallas="interpret"))
    return planned.compile(), (X, w, y)


def _lowered(call):
    """(value, new kernel names, lane-major lowerings) of ``call()``."""
    names = obs.kernel_names()
    before = obs.snapshot().get(obs.ROW_LANES, {"count": 0})["count"]
    out = call()
    after = obs.snapshot().get(obs.ROW_LANES, {"count": 0})["count"]
    return out, obs.kernel_names() - names, after - before


def _orientation(compiled):
    (entry,) = compiled.explain()["execution"]["row_orientation"]
    return entry


def test_column_major_main_selects_lane_major():
    compiled, (X, w, y) = _compiled(1040)
    assert device_layout(_col(X)) == (1, 0) and device_layout(X) == (0, 1)
    assert _orientation(compiled) == {"specs": [0],
                                      "orientation": "by_layout"}
    want = compiled(X, w, y)
    got, names, lanes = _lowered(lambda: compiled(_col(X), w, _col(y)))
    assert lanes == 1
    assert len(names) == 1 and next(iter(names)).startswith("rowt_no_agg_")
    assert _orientation(compiled) == {"specs": [0],
                                      "orientation": "lane_major"}
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_row_major_main_keeps_row_major():
    compiled, (X, w, y) = _compiled(1048)
    _out, names, lanes = _lowered(lambda: compiled(X, w, y))
    assert lanes == 0
    assert [n.split("_")[0] for n in names] == ["row"]
    e = _orientation(compiled)
    assert e["orientation"] == "row_major"
    assert "major_to_minor (0, 1)" in e["reason"]


def test_tracer_keeps_row_major():
    """Inside an outer ``jit`` the main is a tracer: its layout cannot be
    read, so the kernel stays row-major."""
    compiled, (X, w, y) = _compiled(1056)
    _out, names, lanes = _lowered(
        lambda: jax.jit(lambda a, b, c: compiled(a, b, c))(_col(X), w,
                                                           _col(y)))
    assert lanes == 0
    assert all(not n.startswith("rowt_") for n in names)
    e = _orientation(compiled)
    assert e["orientation"] == "row_major" and "not readable" in e["reason"]


def test_explain_lists_row_kernels_of_distributed_specs_run_locally():
    """Under an abstract mesh the ``distributed`` Row spec runs as a
    local fused kernel, so ``explain()`` lists its orientation, before a
    call and after one whose main is stored column-major."""
    from repro.dist import LogicalMesh
    X, w, y = _operands(4096)
    region = fused(lambda X, w, y: X.T @ (ir.relu(1.0 - y * (X @ w)) * y))
    compiled = region.trace(X, w, y).plan(context=FusionContext(
        pallas="interpret", layout=LogicalMesh({"data": 4}))).compile()
    (idx,) = [j for j, s in enumerate(compiled.planned.eplan.specs)
              if getattr(getattr(s, "placement", None), "arm", None)
              == "distributed"]
    assert any(f["site"] == "operator"
               for f in compiled.explain()["execution"]["fallbacks"])

    def entry():
        (e,) = [e for e in compiled.explain()["execution"]["row_orientation"]
                if e["specs"] == [idx]]
        return e

    assert entry() == {"specs": [idx], "orientation": "by_layout"}
    got = compiled(_col(X), w, _col(y))
    assert entry() == {"specs": [idx], "orientation": "lane_major"}
    want = X.T @ (jnp.maximum(1.0 - y * (X @ w), 0.0) * y)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-3)


def test_vmap_path_keeps_row_major():
    """The serving tier's batched path vmaps the row-major lowering."""
    compiled, (X, w, y) = _compiled(1064)
    batched = compiled.batched()
    stacked = [jnp.stack([a, a]) for a in (X, w, y)]
    order = dict(zip("Xwy", stacked))
    _out, names, lanes = _lowered(
        lambda: batched(*[order[n] for n in compiled.input_order]))
    assert lanes == 0
    assert names and all(n.startswith("row_") for n in names)


def test_staged_key_separates_orientations():
    """One plan holds both orientations: two staged functions under two
    whole-plan keys, the row-major one keyed exactly as before."""
    compiled, (X, w, y) = _compiled(1072)
    cp = compiled._cplan
    order = dict(zip("Xwy", (X, w, y)))
    rows = [order[n] for n in compiled.input_order]
    cols = [_col(a) if a.shape[0] > 1 and a is not w else a for a in rows]
    fn_rows, _ = cp.staged_callable(rows)
    fn_cols, _ = cp.staged_callable(cols)
    assert fn_rows is not fn_cols
    assert fn_rows is cp.staged_callable()[0]
    assert len(cp._staged) == 2
    lanes = cp.lane_inputs(cols)
    key_rows = cp._staged[frozenset()][2]
    key_cols = cp._staged[lanes][2]
    assert key_rows == cp.table.key
    assert key_cols[:-1] == key_rows
    assert key_cols[-1] == ("rowt", tuple(sorted(lanes)))
    assert WHOLE_PLAN_CACHE.get(key_rows) is fn_rows
    assert WHOLE_PLAN_CACHE.get(key_cols) is fn_cols


def test_shape_only_operands_read_the_default_layout():
    """An AOT compile passes shapes: a ``ShapeDtypeStruct`` with a
    layout of its own gives it; one with only a device gives the
    device's default layout for its shape (row-major on the CPU), as
    does a host array for the default device; one with neither has no
    readable layout."""
    col = jax.ShapeDtypeStruct((64, N), jnp.float32, sharding=Format(
        Layout(major_to_minor=(1, 0)), _dev()))
    default = jax.ShapeDtypeStruct((64, N), jnp.float32, sharding=_dev())
    bare = jax.ShapeDtypeStruct((64, N), jnp.float32)
    assert device_layout(col) == (1, 0)
    assert device_layout(default) == (0, 1)
    assert device_layout(np.zeros((64, N), np.float32)) == (0, 1)
    assert device_layout(bare) is None


def test_no_transposed_form_is_a_recorded_reason():
    """A program op without a transposed form keeps the row-major
    lowering, and explain() names why."""
    f = fused(lambda X, V: (X @ (V * 2.0).T).rowsums())
    X = jnp.asarray(rng.normal(size=(96, N)), jnp.float32)
    V = jnp.asarray(rng.normal(size=(3, N)), jnp.float32)
    compiled = f.trace(X, V).plan(
        context=FusionContext(pallas="interpret")).compile()
    e = _orientation(compiled)
    assert e["orientation"] == "row_major"
    assert e["reason"].endswith("transposes a resident operand the "
                                "kernel computes"), e
    want = compiled(X, V)
    got, names, lanes = _lowered(lambda: compiled(_col(X), V))
    assert lanes == 0 and all(n.startswith("row_") for n in names)
    assert _orientation(compiled) == e
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
