"""Compile the generated Pallas kernels for a TPU v5e without a chip.

Each test plans a fused region at a real width (the Mnist8m shape: 784
columns), lowers its staged whole-plan function with ``pallas="tpu"``
against a described ``v5e:2x2`` topology, and compiles it — what Mosaic
refuses there (unaligned blocks, too much VMEM) it would refuse on the
chip.  Nothing runs.  The topology is described inside a fixture only,
so every test worker collects the same tests and only the worker that
runs this file loads the TPU compiler.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import obs
from repro.core import FusionContext, fused, ir
from repro.core.codegen import PLAN_CACHE
from repro.core.cost import FusedOpSpec
from repro.core.select import MultiAggSpec
from repro.kernels.blocksparse import BCSR

N = 784                       # Mnist8m features
#: one chip's quarter of Mnist8m's 8.1M rows, and the shard-local rows of
#: the four-chip run when its state is halved to fit (chip_smoke.py)
ROWS = (2_025_000, 1_011_712)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:            # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # entries compiled for a described chip cannot be read back without
    # one; keep them out of any persistent cache the environment enables
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _sds(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_tpu(region, operands, sharding):
    """Plan ``region`` at the operands' shapes under ``pallas="tpu"``,
    compile its staged function for the described chip, and return
    ((template, variant) of every fused operator, fallbacks, HLO text)."""
    planned = region.trace(*operands).plan(
        context=FusionContext(pallas="tpu"))
    graph = planned.eplan.graph
    kinds = []
    for spec in planned.eplan.specs:
        if isinstance(spec, MultiAggSpec) or (
                isinstance(spec, FusedOpSpec) and spec.fused):
            _op, cp = PLAN_CACHE.get_or_build(graph, spec)
            kinds.append((cp.ttype.name, cp.variant, bool(cp.extra)))
    cplan = planned.compile()._cplan
    _fn, raw = cplan.staged_callable()
    by_name = dict(zip(region.names, operands))
    args = [jax.tree_util.tree_map(lambda s: _sds(s.shape, sharding, s.dtype),
                                   by_name[nd.name])
            for nd in graph.inputs()]
    hlo = jax.jit(raw).lower(*args).compile().as_text()
    fallbacks = planned.explain()["execution"]["fallbacks"] + cplan.fallbacks
    return kinds, fallbacks, hlo


def _S(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


@pytest.mark.parametrize("m", ROWS)
def test_cell_full_agg_compiles(one_chip, m):
    region = fused(lambda X, y: (ir.relu(X - 1.0) * y).sum())
    kinds, fbs, hlo = _compile_tpu(region, (_S(m, N), _S(m, 1)), one_chip)
    assert kinds == [("MAGG", "full_agg", False)]     # Cell skeleton
    assert fbs == []
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("m", ROWS)
def test_row_col_t_agg_compiles(one_chip, m):
    region = fused(lambda X, v, P: X.T @ (P * (X @ v)))
    kinds, fbs, hlo = _compile_tpu(
        region, (_S(m, N), _S(N, 10), _S(m, 10)), one_chip)
    assert kinds == [("ROW", "col_t_agg", False)]
    assert fbs == []
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("m", ROWS)
def test_multi_agg_compiles(one_chip, m):
    region = fused(lambda X, y: ((X * y).sum(), (X * X).sum()))
    kinds, fbs, hlo = _compile_tpu(region, (_S(m, N), _S(m, 1)), one_chip)
    assert kinds == [("MAGG", "full_agg", True)]
    assert fbs == []
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("m", ROWS)
def test_outer_right_mm_compiles(one_chip, m):
    # 8 is the largest block that divides both 784 and the row counts
    bs, nb, r = 8, 4096, 8
    X = BCSR(jax.ShapeDtypeStruct((nb, bs, bs), jnp.float32),
             jax.ShapeDtypeStruct((nb,), jnp.int32),
             jax.ShapeDtypeStruct((nb,), jnp.int32), (m, N), bs)
    region = fused(lambda X, U, V: (ir.neq0(X) * (U @ V.T)) @ V,
                   sparsity={"X": 0.01})
    kinds, fbs, hlo = _compile_tpu(region, (X, _S(m, r), _S(N, r)),
                                   one_chip)
    assert kinds == [("OUTER", "right_mm", False)]
    assert fbs == []
    assert "tpu_custom_call" in hlo


def test_kernel_name_in_the_hlo(one_chip):
    """The Row kernel's name (``repro.obs.kernel_name``) names its
    custom-call instruction in the compiled HLO, which is the op name a
    TPU trace reports.  The row count is this test's own, so the kernel
    is lowered, and named, here."""
    m = 8192
    region = fused(lambda X, v, P: X.T @ (P * (X @ v)))
    before = obs.kernel_names()
    kinds, _fbs, hlo = _compile_tpu(
        region, (_S(m, N), _S(N, 10), _S(m, 10)), one_chip)
    (name,) = obs.kernel_names() - before
    assert kinds == [("ROW", "col_t_agg", False)]
    assert name.startswith("row_col_t_agg_")
    assert re.search(rf"%{name}(\.\d+)? = \S+ custom-call\(", hlo)


#: one chip's rows of the benchmark's L2SVM cell
CELL_ROWS = 1_011_712


@pytest.mark.parametrize("program", ["hinge", "objective", "objective.grad"])
def test_l2svm_programs_read_x_in_place(one_chip, program):
    """The described v5e stores X, f32[1011712, 784], and the (m, 1)
    vectors column-major by default, so the L2SVM programs' Row kernels
    lower lane-major and name themselves ``rowt_*``: the compiled HLO
    has no copy or transpose that produces an f32[1011712,784] value,
    and the forward programs' kernels read X through a bitcast (the
    backward's Row kernels are over the (m, 1) label vector)."""
    from repro.algos import l2svm
    m = CELL_ROWS
    region, shapes = {
        "hinge": (l2svm._hinge, ((m, N), (N, 1), (m, 1))),
        "objective": (l2svm._objective_full, ((m, N), (N, 1), (m, 1), (1, 1))),
        "objective.grad": (l2svm._objective_full,
                           ((m, N), (N, 1), (m, 1), (1, 1))),
    }[program]
    compiled = region.trace(*[_S(*s) for s in shapes]).plan(
        context=FusionContext(pallas="tpu")).compile()
    cplan = (compiled._get_bwd()[0] if program.endswith(".grad")
             else compiled._cplan)
    args = [_sds(tuple(nd.shape), one_chip)
            for nd in cplan.plan.graph.inputs()]
    assert cplan.lane_inputs(args), "no column-major Row main"
    _fn, raw = cplan.staged_callable(args)
    hlo = jax.jit(raw).lower(*args).compile().as_text()
    x = rf"f32\[{m},{N}\]\{{[^}}]*\}}"
    assert not re.search(rf"= {x} (copy|transpose)\(", hlo)
    if not program.endswith(".grad"):
        assert re.search(rf"= f32\[{N},{m}\]\{{[^}}]*\}} bitcast\(", hlo)
    kernels = re.findall(r"%(\w+?)(?:\.\d+)? = \S+ custom-call\(", hlo)
    rows = [k for k in kernels if k.startswith(("row_", "rowt_"))]
    assert rows and all(k.startswith("rowt_") for k in rows), kernels


#: one chip's rows of the benchmark's MLogReg cell: a four-chip host's
#: 4-way share of Mnist8m (2 x CELL_ROWS)
MLOGREG_ROWS = 2_023_424


@pytest.mark.parametrize("program", ["probs", "objective", "objective.grad",
                                     "hvp"])
def test_mlogreg_programs_fit_one_chip_at_the_cell_size(one_chip, program):
    """At 2,023,424 x 784 x 10 the four programs of an MLogReg fit
    compile for the described v5e with no copy or transpose that produces
    an f32[2023424,784] value, the HVP lowers to one lane-major
    ``col_t_agg`` Row kernel, and each program's arguments, outputs and
    temporaries fit in 13.5e9 bytes of the chip's 16 GB (the backward,
    X and Y in and dX, dY and dB out, is the largest)."""
    from repro.algos import mlogreg
    m, k = MLOGREG_ROWS, 10
    region, shapes = {
        "probs": (mlogreg._probs, ((m, N), (N, k))),
        "objective": (mlogreg._nll_obj_reg, ((m, N), (N, k), (m, k), (1, 1))),
        "objective.grad": (mlogreg._nll_obj_reg,
                           ((m, N), (N, k), (m, k), (1, 1))),
        "hvp": (mlogreg._hvp, ((m, N), (N, k), (m, k))),
    }[program]
    compiled = region.trace(*[_S(*s) for s in shapes]).plan(
        context=FusionContext(pallas="tpu")).compile()
    cplan = (compiled._get_bwd()[0] if program.endswith(".grad")
             else compiled._cplan)
    args = [_sds(tuple(nd.shape), one_chip)
            for nd in cplan.plan.graph.inputs()]
    _fn, raw = cplan.staged_callable(args)
    exe = jax.jit(raw).lower(*args).compile()
    hlo = exe.as_text()
    x = rf"f32\[{m},{N}\]\{{[^}}]*\}}"
    assert not re.search(rf"= {x} (copy|transpose)\(", hlo)
    kernels = re.findall(r"%(\w+?)(?:\.\d+)? = \S+ custom-call\(", hlo)
    if program == "hvp":
        assert len(kernels) == 1 and kernels[0].startswith(
            "rowt_col_t_agg_"), kernels
    mem = exe.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used <= 13.5e9, used


@pytest.mark.parametrize("algo", ["l2svm", "mlogreg"])
def test_weight_only_backward_reads_x_once(one_chip, algo):
    """The backward the fits run, planned for the weights alone, at the
    cells' sizes: one lane-major ``rowt_col_t_agg_*`` Row kernel reads X
    through a bitcast, and nothing of X's shape is written, copied or
    transposed (no dX).  MLogReg's program fits in 7.5e9 bytes, about X
    alone, where the all-inputs backward needs 12.95e9."""
    from repro.algos import l2svm, mlogreg
    region, m, k, w = {
        "l2svm": (l2svm._objective_full, CELL_ROWS, 1, "w"),
        "mlogreg": (mlogreg._nll_obj_reg, MLOGREG_ROWS, 10, "B"),
    }[algo]
    shapes = ((m, N), (N, k), (m, k), (1, 1))
    compiled = region.trace(*[_S(*s) for s in shapes]).plan(
        context=FusionContext(pallas="tpu")).compile()
    cplan, grad_names, _cts = compiled._get_bwd([w])
    assert grad_names == [w]
    assert cplan.name == obs.program_name(region.fn.__name__, backward=True)
    args = [_sds(tuple(nd.shape), one_chip)
            for nd in cplan.plan.graph.inputs()]
    _fn, raw = cplan.staged_callable(args)
    exe = jax.jit(raw).lower(*args).compile()
    hlo = exe.as_text()
    x = rf"f32\[{m},{N}\]\{{[^}}]*\}}"
    assert not re.search(rf"= {x} (copy|transpose)\(", hlo)
    assert not re.search(rf"->.*f32\[{m},{N}\]", hlo.splitlines()[0])
    assert re.search(rf"= f32\[{N},{m}\]\{{[^}}]*\}} bitcast\(", hlo)
    kernels = re.findall(r"%(\w+?)(?:\.\d+)? = \S+ custom-call\(", hlo)
    rows = [n for n in kernels if n.startswith(("row_", "rowt_"))]
    assert len(rows) == 1 and rows[0].startswith("rowt_col_t_agg_"), kernels
    mem = exe.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert mem.output_size_in_bytes < 1e6, mem       # the gradient alone
    if algo == "mlogreg":
        assert used <= 7.5e9, used
