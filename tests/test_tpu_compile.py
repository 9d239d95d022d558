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

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

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
