"""Generated Pallas kernels inside staged and distributed plans.

The silent-fallback fix (shard-local BlockSpecs): fused bodies run as
``pallas_call`` *inside* ``shard_map`` segments instead of silently
downgrading to XLA or per-operator dispatch.  Three layers of proof:

* an in-process parity sweep — dense × BCSR operands across the
  Cell/Row/Outer/MultiAgg templates, ``pallas="interpret"`` vs
  ``pallas="never"`` on the same staged plan, 1e-5;
* jaxpr witnesses — the staged whole-plan trace contains ``pallas_call``,
  and on a real 8-device mesh (subprocess, forced host devices) it sits
  *inside* the ``shard_map`` region;
* the distributed BCSR-main path — an Outer-template plan whose sparse
  main block-row-partitions across 8 shards compiles staged with zero
  recorded fallbacks, and when the operand *cannot* partition the
  downgrade carries a reason (and raises under ``verify="strict"``).
"""

import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Fused, fused, ir
from repro.kernels.blocksparse import BCSR

rng = np.random.default_rng(9)


def arr(*shape):
    return jnp.asarray(rng.normal(size=shape), jnp.float32)


def _bcsr(m, n, bs, density):
    mask = rng.random((m // bs, n // bs)) < density
    mask.flat[0] = True
    dense = (rng.normal(size=(m, n))
             * np.kron(mask, np.ones((bs, bs)))).astype(np.float32)
    return BCSR.from_dense(dense, bs=bs), jnp.asarray(dense)


# --------------------------------------------------------------------------
# interpret-vs-never parity sweep: dense × BCSR × every template
# --------------------------------------------------------------------------

def _cases():
    X, Y = arr(64, 48), arr(64, 48)
    v = arr(48, 3)
    Xs, _ = _bcsr(64, 48, 16, 0.3)
    Xo, _ = _bcsr(1024, 512, 128, 0.05)
    U, V = arr(1024, 8), arr(512, 8)
    return {
        "cell_noagg_dense":
            ("CELL", fused(lambda X, Y: ir.abs_(X) * Y + 2.0),
             dict(X=X, Y=Y)),
        "row_dense":
            ("ROW", fused(lambda X, v: ((X @ v) * 2.0).rowsums()),
             dict(X=X, v=v)),
        "magg_single_dense":
            ("MAGG", fused(lambda X, Y: (X * Y + 1.0).sum()),
             dict(X=X, Y=Y)),
        "magg_multi_dense":
            ("MAGG(multi)",
             fused(lambda X, Y: ((X * Y).sum(), (X ** 2).sum(),
                                 ir.abs_(Y).max_())),
             dict(X=X, Y=Y)),
        "magg_bcsr":
            ("MAGG", fused(lambda X, Y: (X * Y).sum()), dict(X=Xs, Y=Y)),
        "outer_bcsr_right_mm":
            ("OUTER",
             Fused(lambda X, U, V: (ir.neq0(X) * (U @ V.T)) @ V,
                   sparsity={"X": 0.05}),
             dict(X=Xo, U=U, V=V)),
        "outer_bcsr_full_agg":
            ("OUTER",
             Fused(lambda X, U, V: (ir.neq0(X) * (U @ V.T)).sum(),
                   sparsity={"X": 0.05}),
             dict(X=Xo, U=U, V=V)),
    }


@pytest.mark.parametrize("name", sorted(_cases()))
def test_interpret_parity_by_template(name):
    """Same staged plan, ``pallas="interpret"`` vs ``pallas="never"``:
    the generated kernel and the XLA lowering agree to 1e-5, and the
    plan picks the intended template."""
    template, f, args = _cases()[name]
    planned = f.trace(**args).plan(mode="gen")
    ops = planned.explain()["winner"]["operators"]
    assert [o["template"] for o in ops] == [template], ops
    got = planned.compile(pallas="interpret")(**args)
    ref = planned.compile(pallas="never")(**args)
    got = got if isinstance(got, tuple) else (got,)
    ref = ref if isinstance(ref, tuple) else (ref,)
    for a, b in zip(got, ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


def test_staged_jaxpr_contains_pallas_call():
    """``pallas="interpret"`` staged plans actually trace the generated
    kernel — the whole-plan jaxpr contains a ``pallas_call``."""
    X, v = arr(64, 48), arr(48, 3)
    f = fused(lambda X, v: ((X @ v) * 2.0).rowsums())
    compiled = f.trace(X, v).plan(mode="gen").compile(pallas="interpret")
    compiled(X, v)
    _fn, raw = compiled._cplan.staged_callable()
    assert "pallas_call" in str(jax.make_jaxpr(raw)(X, v))
    assert compiled._cplan.fallbacks == []


# --------------------------------------------------------------------------
# real-mesh subprocess harness (8 forced host devices)
# --------------------------------------------------------------------------

def _run_forced_devices(prog: str) -> None:
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=str(Path(__file__).resolve().parents[1] / "src"))
    res = subprocess.run([sys.executable, "-c", prog],
                         capture_output=True, text=True, timeout=300,
                         env=env)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "OK" in res.stdout


# jaxpr helpers shared by the subprocess programs: find a pallas_call
# nested anywhere inside a shard_map equation's body
_JAXPR_HELPERS = """
def _subjaxprs(jx):
    for eqn in jx.eqns:
        for v in eqn.params.values():
            if hasattr(v, "jaxpr"):
                yield v.jaxpr
            elif hasattr(v, "eqns"):
                yield v

def _count(jx, name):
    c = sum(1 for eqn in jx.eqns if name in eqn.primitive.name)
    for sub in _subjaxprs(jx):
        c += _count(sub, name)
    return c

def _pallas_inside_shard_map(jx):
    for eqn in jx.eqns:
        inner = [v.jaxpr if hasattr(v, "jaxpr") else v
                 for v in eqn.params.values()
                 if hasattr(v, "jaxpr") or hasattr(v, "eqns")]
        if "shard_map" in eqn.primitive.name:
            if any(_count(sub, "pallas_call") > 0 for sub in inner):
                return True
        if any(_pallas_inside_shard_map(sub) for sub in inner):
            return True
    return False
"""


_SEGMENT_PALLAS_PROG = """
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh
from repro.core import fused, ir

assert len(jax.devices()) == 8, jax.devices()
mesh = Mesh(np.array(jax.devices()).reshape(8), ("data",))
""" + _JAXPR_HELPERS + """
def expr(X1, X2, X3, X4, X5, X6, w):
    A = ir.sigmoid(X1 + X2 + X3 + X4 + X5 + X6)
    return ((A * X1 + X2).sum(), (A - X3).rowsums(),
            (A * A + X4).sum(), (w ** 2).sum())

f = fused(expr)
m, n = 4096, 64
rng = np.random.default_rng(11)
Xs = [jnp.asarray(rng.normal(size=(m, n)), jnp.float32) for _ in range(6)]
w = jnp.asarray(rng.normal(size=(10, 1)), jnp.float32)
tr = f.trace(*Xs, w)
planned = tr.plan(mode="gen", layout=mesh)
segs = planned.explain()["distributed"]["segments"]
assert len(segs) == 1 and segs[0]["n_operators"] >= 2, segs

compiled = planned.compile(pallas="interpret")
outs = compiled(*Xs, w)
assert compiled._cplan.fallbacks == [], compiled._cplan.fallbacks

_fn, raw = compiled._cplan.staged_callable()
jaxpr = jax.make_jaxpr(raw)(*Xs, w)
assert _pallas_inside_shard_map(jaxpr.jaxpr), \\
    "no pallas_call inside a shard_map region"

local = tr.plan(mode="gen").compile(pallas="never")(*Xs, w)
for a, b in zip(outs, local):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-5)
print("OK")
"""


def test_segment_runs_pallas_inside_shard_map():
    """On a real 8-device mesh a multi-operator distributed segment
    executes its generated kernels as ``pallas_call`` *inside* the
    ``shard_map`` region (jaxpr inspection), with 1e-5 parity against
    the local ``pallas="never"`` plan and zero recorded fallbacks."""
    _run_forced_devices(_SEGMENT_PALLAS_PROG)


_DIST_BCSR_PROG = """
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh
from repro.core import Fused, ir
from repro.kernels.blocksparse import BCSR

assert len(jax.devices()) == 8, jax.devices()
mesh = Mesh(np.array(jax.devices()).reshape(8), ("data",))
""" + _JAXPR_HELPERS + """
rng = np.random.default_rng(13)
m, n, bs = 2048, 512, 128                  # mb=16: 2 block rows per shard
mask = rng.random((m // bs, n // bs)) < 0.05
mask.flat[0] = True
Xd = (rng.normal(size=(m, n))
      * np.kron(mask, np.ones((bs, bs)))).astype(np.float32)
X = BCSR.from_dense(Xd, bs=bs)
U = jnp.asarray(rng.normal(size=(m, 8)), jnp.float32)
V = jnp.asarray(rng.normal(size=(n, 8)), jnp.float32)

f = Fused(lambda X, U, V: (ir.neq0(X) * (U @ V.T)) @ V,
          sparsity={"X": 0.05})
planned = f.trace(X=X, U=U, V=V).plan(mode="gen", layout=mesh)
ops = planned.explain()["winner"]["operators"]
assert [(o["template"], o.get("placement")) for o in ops] \\
    == [("OUTER", "distributed")], ops

compiled = planned.compile(pallas="interpret")
out = compiled(X=X, U=U, V=V)
assert compiled._cplan._staged_fn is not None          # staged, not per-op
assert compiled._cplan.fallbacks == [], compiled._cplan.fallbacks
assert compiled.explain()["execution"]["fallbacks"] == []

ref = (np.where(Xd != 0, 1.0, 0.0).astype(np.float32)
       * (np.asarray(U) @ np.asarray(V).T)) @ np.asarray(V)
np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-5, atol=1e-5)
print("OK")
"""


def test_distributed_bcsr_main_compiles_staged():
    """A distributed Outer-template plan with a BCSR main partitions the
    sparse operand block-row-wise across the 8 shards, compiles staged,
    records zero fallbacks, and matches the dense reference to 1e-5."""
    _run_forced_devices(_DIST_BCSR_PROG)


_STRICT_PROG = """
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh
from repro.core import Fused, FusionContext, ir
from repro.core.partitions import PlanInvariantError
from repro.kernels.blocksparse import BCSR

assert len(jax.devices()) == 8
mesh = Mesh(np.array(jax.devices()).reshape(8), ("data",))
rng = np.random.default_rng(17)
m, n, bs = 1536, 512, 128       # mb=12: rows divide 8, block rows do not
mask = rng.random((m // bs, n // bs)) < 0.05
mask.flat[0] = True
Xd = (rng.normal(size=(m, n))
      * np.kron(mask, np.ones((bs, bs)))).astype(np.float32)
X = BCSR.from_dense(Xd, bs=bs)
U = jnp.asarray(rng.normal(size=(m, 8)), jnp.float32)
V = jnp.asarray(rng.normal(size=(n, 8)), jnp.float32)

f = Fused(lambda X, U, V: (ir.neq0(X) * (U @ V.T)) @ V,
          sparsity={"X": 0.05})
planned = f.trace(X=X, U=U, V=V).plan(mode="gen", layout=mesh)
assert [o.get("placement") for o in
        planned.explain()["winner"]["operators"]] == ["distributed"]

# default: runs correctly, the downgrade is recorded WITH a reason
compiled = planned.compile(pallas="interpret")
out = compiled(X=X, U=U, V=V)
fbs = compiled.explain()["execution"]["fallbacks"]
assert fbs and all(str(fb.get("reason", "")).strip() for fb in fbs), fbs
assert any("not partitionable" in fb["reason"] for fb in fbs), fbs
# float64 reference; f32 accumulation error scales with the output's
# magnitude, so the absolute tolerance is relative to max|ref|
U64, V64 = np.asarray(U, np.float64), np.asarray(V, np.float64)
ref = (np.where(Xd != 0, 1.0, 0.0) * (U64 @ V64.T)) @ V64
np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-5,
                           atol=1e-5 * np.abs(ref).max())

# strict: abandoning the costed distributed placement raises
try:
    with FusionContext(mode="gen", layout=mesh, verify="strict",
                       pallas="interpret"):
        f.trace(X=X, U=U, V=V).plan().compile()(X=X, U=U, V=V)
except PlanInvariantError as e:
    assert "abandoned at execution time" in str(e), e
else:
    raise SystemExit("strict did not raise on the abandoned placement")
print("OK")
"""


def test_strict_raises_on_abandoned_distributed_placement():
    """When a costed distributed placement cannot execute (sparse main
    whose block rows don't divide across the shards), the default mode
    records an explained downgrade and still computes the right answer;
    ``verify="strict"`` raises ``PlanInvariantError`` instead."""
    _run_forced_devices(_STRICT_PROG)
