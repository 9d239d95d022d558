"""Dispatch layer for generated fused operators.

Given a CPlan and bound inputs, pick an execution path:

* **dense / XLA** — interpret the program at trace time (ref.execute_dense);
  XLA emits one fused computation.  Default on CPU.
* **dense / Pallas** — template-skeleton TPU kernels with explicit VMEM
  BlockSpecs (cellwise/rowwise/multiagg); ``interpret=True`` on CPU.
* **BCSR** — sparsity-exploiting paths over non-zero blocks only: the Outer
  template (SDDMM-style) and sparse-safe Cell/MAgg chains.  jnp (gather +
  segment-sum) and Pallas (scalar-prefetch grid) variants.
* **CLA** — DictCompressed single-input sum-aggregate chains evaluated
  over the per-column dictionaries and aggregated via counts (paper
  Fig. 9); the exact qualification rule is documented on
  :func:`_execute_dict`, the format in :mod:`repro.kernels.blocksparse`
  ("CLA compression").

Also hosts block-sparse *basic* operators (sparse matmul etc.) used when a
plan leaves a sparse op unfused.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro import faults
from repro.core.cplan import (CPlan, COL_AGG, FULL_AGG, LEFT_MM, NO_AGG,
                              RIGHT_MM, ROW_AGG)
from repro.core.templates import TType
from . import ref
from .blocksparse import BCSR, DictCompressed

faults.register_site(
    "kernels.pallas_call",
    "generated-kernel dispatch when a Pallas path is selected "
    "(pallas != 'never'): fires while the fused operator is traced into "
    "the surrounding jit, i.e. at build time of the enclosing plan",
    kinds=("error", "latency"),
    handler="per-plan: FusionServer build ladder retries the plan at a "
            "lower tier; per-op: compile_plan(strict) surfaces the error "
            "to the caller — never cached, retries re-dispatch")


# --------------------------------------------------------------------------
# public entry: execute a CPlan on bound values
# --------------------------------------------------------------------------

def execute(cplan: CPlan, env: dict[int, object], *,
            pallas: str = "never", lanes: bool = False) -> jnp.ndarray:
    """Run one fused operator.  ``pallas`` ∈ {"never","interpret","tpu"}.
    ``lanes`` lowers a dense Row kernel in its lane-major orientation
    (rows on lanes, :func:`repro.kernels.rowwise.row_pallas`); the
    staged plan sets it where the main's device layout is column-major.

    Inside a ``shard_map`` body the operands are the shard-local panels,
    so the Pallas template lowerings derive their grids and BlockSpecs
    from the shard-local shapes and lower as ``pallas_call`` inside the
    region.  A dense operator whose kernel cannot be tiled at its shapes
    takes its XLA body; :func:`kernel_fallback` names the reason, which
    ``explain()["execution"]["fallbacks"]`` reports."""
    if pallas != "never":
        faults.fault_point("kernels.pallas_call")
    main = env.get(cplan.main.nid)
    if isinstance(main, DictCompressed):
        out = _execute_dict(cplan, env)
        if out is not None:
            return out
        env = dict(env)
        env[cplan.main.nid] = main.todense()
        main = env[cplan.main.nid]
    if isinstance(main, BCSR):
        has_mm = any(op == "matmul" for (_, op, *_rest) in cplan.prog)
        if cplan.main.exploit and (cplan.ttype == TType.OUTER or not has_mm):
            if pallas != "never" and cplan.ttype == TType.OUTER \
                    and cplan.variant in (RIGHT_MM, FULL_AGG):
                from .outerprod import outer_pallas
                return outer_pallas(cplan, env,
                                    interpret=pallas == "interpret")
            return _execute_bcsr(cplan, env)
        env = dict(env)
        env[cplan.main.nid] = main.todense()   # not exploitable: decompress
    env = {k: (v.todense() if isinstance(v, (BCSR, DictCompressed)) else v)
           for k, v in env.items()}
    lanes = lanes and cplan.ttype == TType.ROW and not cplan.extra
    if pallas != "never" and kernel_fallback(
            cplan, {b.nid: env[b.nid].shape for b in cplan.binds},
            lanes=lanes) is None:
        from . import cellwise, multiagg, rowwise
        interpret = pallas == "interpret"
        if cplan.extra:
            return multiagg.multiagg_pallas(cplan, env, interpret=interpret)
        if cplan.ttype == TType.ROW:
            return rowwise.row_pallas(cplan, env, interpret=interpret,
                                      lanes=lanes)
        return cellwise.cell_pallas(cplan, env, interpret=interpret)
    return ref.execute_dense(cplan, env)


def kernel_fallback(cplan: CPlan, shapes: dict,
                    lanes: bool = False) -> Optional[str]:
    """Why a dense fused operator takes its XLA body instead of its Pallas
    kernel at these operand shapes (nid -> (rows, cols)), or None when
    the kernel applies.  The pipelined blocks may fill half of the
    chip's scoped VMEM; the program's tile temporaries get the rest.
    ``lanes`` reckons a Row kernel's blocks in its lane-major
    orientation (:mod:`repro.kernels.rowwise`)."""
    from .cellwise import VMEM_BLOCK_BUDGET, cell_blocks, vmem_bytes
    from .rowwise import row_blocks
    if cplan.ttype == TType.OUTER:
        return ("Outer template over a dense main: the Outer kernel is "
                "sparsity-exploiting only (BCSR mains); XLA body")
    if cplan.extra or cplan.ttype in (TType.CELL, TType.MAGG):
        blocks = cell_blocks(cplan, shapes)[2]
    else:
        blocks = row_blocks(cplan, shapes, lanes=lanes)[1]
    need, budget = vmem_bytes(blocks), VMEM_BLOCK_BUDGET
    if need > budget:
        return (f"{cplan.ttype.name} blocks {blocks} need {need} B of VMEM "
                f"> {budget} B: no (8,128)-aligned block divides "
                f"{shapes[cplan.main.nid]}; XLA body")
    return None


# --------------------------------------------------------------------------
# BCSR sparsity-exploiting execution (jnp path; Pallas variant in
# outerprod.py is selected by the benchmarks/tests explicitly)
# --------------------------------------------------------------------------

def _gather_blocks(x: jnp.ndarray, idx: jnp.ndarray, bs: int,
                   axis: int) -> jnp.ndarray:
    """Gather (nb, bs, k) row-panels (axis=0) or (nb, k, bs) col-panels."""
    if axis == 0:
        panels = x.reshape(x.shape[0] // bs, bs, x.shape[1])
        return panels[idx]
    panels = x.reshape(x.shape[0], x.shape[1] // bs, bs).transpose(1, 0, 2)
    return panels[idx]


def _block_env(cplan: CPlan, env: dict[int, object], X: BCSR):
    """Per-block views of every bound input: main → (nb,bs,bs) blocks, side
    inputs gathered by block row/col, scalars broadcast."""
    nb, bs = X.nblocks, X.bs
    m, n = X.shape

    def read(nid: int):
        if nid == cplan.main.nid:
            return X.data
        v = env[nid]
        if isinstance(v, (BCSR, DictCompressed)):
            v = v.todense()
        r, c = v.shape
        if (r, c) == (1, 1):
            return v.reshape(1, 1, 1)
        if (r, c) == (m, n):        # aligned matrix: gather (bs,bs) blocks
            blocks = v.reshape(m // bs, bs, n // bs, bs).transpose(0, 2, 1, 3)
            return blocks[X.rows, X.cols]
        if c == 1 and r == m:       # column vector: (nb, bs, 1)
            return v.reshape(m // bs, bs, 1)[X.rows]
        if r == 1 and c == n:       # row vector: (nb, 1, bs)
            return v.reshape(1, n // bs, bs).transpose(1, 0, 2)[X.cols]
        raise NotImplementedError(
            f"side input {v.shape} vs sparse main {X.shape}")

    return read


def _execute_bcsr(cplan: CPlan, env: dict[int, object]) -> jnp.ndarray:
    X: BCSR = env[cplan.main.nid]
    nb, bs = X.nblocks, X.bs
    m, n = X.shape
    read = _block_env(cplan, env, X)

    roots = [cplan.prog_root]
    in_prog = {nid for (nid, *_r) in cplan.prog}
    if cplan.close_nid is not None and cplan.close_nid in in_prog:
        roots.append(cplan.close_nid)

    if cplan.ttype == TType.OUTER:
        fu = _as_dense(env[_kind_nid(cplan, "factor_u")])
        fv = _as_dense(env[_kind_nid(cplan, "factor_v")])
        ub = _gather_blocks(fu, X.rows, bs, 0)       # (nb, bs, r)
        vb = _gather_blocks(fv, X.cols, bs, 0)       # (nb, bs, r)

        def read_outer(nid: int):
            # the outer matmul is evaluated per block: U_bi @ V_bjᵀ
            return read(nid)
        # patch: program contains the outer mm node; intercept by
        # evaluating the program with a special matmul handler
        vals = _apply_prog_blocked(cplan, read_outer, roots, ub, vb)
    else:
        vals = _apply_prog_blocked(cplan, read, roots, None, None)

    val = vals[0]                                     # (nb, bs, bs)
    v = cplan.variant
    if v == FULL_AGG:
        if cplan.extra:
            outs = [_block_agg(vals[0], cplan.agg_op)]
            for x_val, op in zip(vals[1:], [op for _, op in cplan.extra]):
                outs.append(_block_agg(x_val, op))
            return jnp.concatenate(outs, axis=0)
        return _block_agg(val, cplan.agg_op)
    if v == RIGHT_MM:
        closer = _as_dense(env[cplan.close_nid])
        cb = _gather_blocks(closer.T if cplan.close_tb else closer,
                            X.cols, bs, 0)            # (nb, bs, r)
        contrib = jnp.einsum("nij,njk->nik", val, cb)
        out = jax.ops.segment_sum(contrib, X.rows, num_segments=m // bs)
        return out.reshape(m, -1)
    if v == LEFT_MM:
        closer = _as_dense(env[cplan.close_nid])
        cb = _gather_blocks(closer, X.rows, bs, 0)    # (nb, bs, r)
        contrib = jnp.einsum("nij,nik->njk", val, cb)
        out = jax.ops.segment_sum(contrib, X.cols, num_segments=n // bs)
        return out.reshape(n, -1)
    if v == NO_AGG:
        return BCSR(val, X.rows, X.cols, X.shape, bs)
    if v == ROW_AGG:
        assert cplan.agg_op == "sum", "sparse row_agg supports sum"
        s = jnp.sum(val, axis=2)                      # (nb, bs)
        out = jax.ops.segment_sum(s, X.rows, num_segments=m // bs)
        return out.reshape(m, 1)
    if v == COL_AGG:
        assert cplan.agg_op == "sum", "sparse col_agg supports sum"
        s = jnp.sum(val, axis=1)                      # (nb, bs)
        out = jax.ops.segment_sum(s, X.cols, num_segments=n // bs)
        return out.reshape(1, n)
    raise NotImplementedError(f"BCSR variant {v}")


def _apply_prog_blocked(cplan: CPlan, read, roots, ub, vb):
    """Interpret the program with (nb, bs, bs) block values; an interior
    outer matmul evaluates as per-block U_bi @ V_bjᵀ on the MXU."""
    vals: dict[int, jnp.ndarray] = {}
    for (nid, op, ins, _shape, attrs) in cplan.prog:
        attrs = dict(attrs)
        if op == "matmul" and ub is not None:
            # the outer product: U @ t(V) evaluated per non-zero block
            vals[nid] = jnp.einsum("nik,njk->nij", ub, vb)
            continue
        argv = []
        for kind, r in ins:
            if kind == "n":
                argv.append(vals[r])
            elif kind == "b":
                argv.append(read(r))
            else:
                argv.append(r)
        vals[nid] = ref.eval_node(op, argv, attrs)
    return [vals[r] if r in vals else read(r) for r in roots]


def _block_agg(val: jnp.ndarray, op: str) -> jnp.ndarray:
    if op == "sum":
        return jnp.sum(val).reshape(1, 1)
    if op == "min":
        return jnp.min(val).reshape(1, 1)   # pseudo-sparse-safe: min ≤ 0
    if op == "max":
        return jnp.max(val).reshape(1, 1)
    raise NotImplementedError(op)


def _kind_nid(cplan: CPlan, kind: str) -> int:
    for b in cplan.binds:
        if b.kind == kind:
            return b.nid
    raise KeyError(kind)


def _as_dense(v):
    return v.todense() if isinstance(v, (BCSR, DictCompressed)) else v


# --------------------------------------------------------------------------
# CLA (DictCompressed) fast path — paper Fig. 9
# --------------------------------------------------------------------------

def _execute_dict(cplan: CPlan, env) -> Optional[jnp.ndarray]:
    """CLA fast path over a :class:`~repro.kernels.blocksparse.
    DictCompressed` main input: evaluate the program on the per-column
    dictionary values only, then aggregate via the occurrence counts
    (``Σ f(distinct) · count`` — paper Fig. 9).

    A plan qualifies only when the whole chain is a function of the
    compressed matrix and scalars, so per-distinct-value evaluation is
    exact:

    * exactly one non-scalar bound input (the compressed main — any
      matrix/vector side input would need per-cell alignment the
      dictionary has erased),
    * variant ``full_agg`` with ``agg_op == "sum"`` (count-weighted
      reduction; min/max/mean don't weight by counts the same way),
    * not a combined multi-aggregate (``cplan.extra`` empty),
    * every other bound value is a (1, 1) scalar — a non-scalar side
      read makes :func:`read` return None and the program evaluation
      fail, which is caught below.

    Returns the (1, 1) aggregate, or None when the plan does not
    qualify — :func:`execute` then decompresses the main via
    ``todense()`` and re-dispatches on the dense paths.  See the "CLA
    compression" section of :mod:`repro.kernels.blocksparse` for the
    format itself."""
    mats = [b for b in cplan.binds if b.kind != "scalar"]
    if len(mats) != 1 or cplan.variant != FULL_AGG \
            or cplan.agg_op not in ("sum",) or cplan.extra:
        return None
    X: DictCompressed = env[cplan.main.nid]

    def read(nid: int):
        if nid == cplan.main.nid:
            return X.values                 # (ncol, ndist)
        v = env[nid]
        if hasattr(v, "shape") and tuple(v.shape) == (1, 1):
            return v
        return None
    try:
        (val,) = ref.apply_program(cplan, read, [cplan.prog_root])
    except TypeError:
        return None
    return jnp.sum(val * X.counts).reshape(1, 1)


# --------------------------------------------------------------------------
# block-sparse basic operators (for unfused plans over sparse data)
# --------------------------------------------------------------------------

def bcsr_matmul(a: BCSR, b: jnp.ndarray) -> jnp.ndarray:
    """(m,n) BCSR @ (n,k) dense → (m,k) dense."""
    bb = _gather_blocks(b, a.cols, a.bs, 0)           # (nb, bs, k)
    contrib = jnp.einsum("nij,njk->nik", a.data, bb)
    out = jax.ops.segment_sum(contrib, a.rows,
                              num_segments=a.shape[0] // a.bs)
    return out.reshape(a.shape[0], -1)


def bcsr_cellwise(op: str, a: BCSR) -> BCSR:
    """Sparse-safe unary over non-zero blocks."""
    return BCSR(ref.eval_node(op, [a.data], {}), a.rows, a.cols,
                a.shape, a.bs)


def bcsr_mul_dense(a: BCSR, d: jnp.ndarray) -> BCSR:
    m, n = a.shape
    blocks = d.reshape(m // a.bs, a.bs, n // a.bs, a.bs).transpose(0, 2, 1, 3)
    return BCSR(a.data * blocks[a.rows, a.cols], a.rows, a.cols, a.shape,
                a.bs)
