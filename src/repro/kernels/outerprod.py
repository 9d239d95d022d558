"""Pallas TPU skeleton for the **Outer** (sparsity-exploiting) template.

SystemML's SpoofOuterProduct visits each non-zero scalar X_ij, computes
w = U_i·V_jᵀ, applies the generated chain and scatters w⊙V_j.  Scalar
gathers do not exist on TPU, so the adaptation is *block-level SDDMM*: the
grid runs over the non-zero (bs×bs) blocks of a row-major-sorted BCSR; a
scalar-prefetched index list steers the BlockSpec index maps so each step
gathers U[rows[b]], V[cols[b]] panels into VMEM, computes the bs×bs outer
product on the MXU, applies the fused chain, and

  * ``right_mm``  accumulates chain @ V[cols[b]] into out[rows[b]] —
    row-major sorting keeps the output block VMEM-resident across
    consecutive blocks of the same block-row;
  * ``full_agg``  accumulates a (1,1) scalar across all blocks;
  * ``no_agg``    writes the chain back as BCSR block data.

Asymptotics match the paper: work ∝ non-zero blocks, never m×n.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import obs
from repro.core.cplan import (CPlan, FULL_AGG, NO_AGG, RIGHT_MM)
from . import ref
from .blocksparse import BCSR


def outer_pallas(cplan: CPlan, env: dict[int, object], *,
                 interpret: bool = False):
    X: BCSR = env[cplan.main.nid]
    nb, bs = X.nblocks, X.bs
    m, n = X.shape
    variant = cplan.variant

    fu = _bind(cplan, env, "factor_u")
    fv = _bind(cplan, env, "factor_v")
    r = fu.shape[1]
    dtype = X.data.dtype

    # inputs: [rows, cols] scalar-prefetch, then data, U, V, sides...
    side_binds = [b for b in cplan.binds
                  if b.kind in ("side", "scalar")]
    sides = [jnp.asarray(env[b.nid]) for b in side_binds]

    def u_map(b, rows, cols):
        return (rows[b], 0)

    def v_map(b, rows, cols):
        return (cols[b], 0)

    in_specs = [
        pl.BlockSpec((1, bs, bs), lambda b, rows, cols: (b, 0, 0)),  # X data
        pl.BlockSpec((bs, r), u_map),                                # U
        pl.BlockSpec((bs, r), v_map),                                # V
    ]
    for b_, s in zip(side_binds, sides):
        sr, sc = s.shape
        if (sr, sc) == (1, 1):
            in_specs.append(pl.BlockSpec((1, 1), lambda b, rows, cols: (0, 0)))
        elif (sr, sc) == (m, n):
            in_specs.append(pl.BlockSpec(
                (bs, bs), lambda b, rows, cols: (rows[b], cols[b])))
        elif sc == 1 and sr == m:
            in_specs.append(pl.BlockSpec((bs, 1), u_map))
        elif sr == 1 and sc == n:
            in_specs.append(pl.BlockSpec(
                (1, bs), lambda b, rows, cols: (0, cols[b])))
        else:
            raise NotImplementedError(f"outer side input {s.shape}")
    nid_to_pos = {b.nid: i + 3 for i, b in enumerate(side_binds)}

    if variant == RIGHT_MM:
        closer = _dense(env[cplan.close_nid])
        if cplan.close_tb:
            closer = closer.T
        k_out = closer.shape[1]
        in_specs.append(pl.BlockSpec((bs, k_out), v_map))   # V-side gather
        # zeros aliased to the output, never read by the kernel: block
        # rows no grid step visits keep them
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        out_spec = pl.BlockSpec((bs, k_out), u_map)
        out_shape = jax.ShapeDtypeStruct((m, k_out), dtype)
    elif variant == FULL_AGG:
        closer = None
        out_spec = pl.BlockSpec((1, 1), lambda b, rows, cols: (0, 0))
        out_shape = jax.ShapeDtypeStruct((1, 1), dtype)
    elif variant == NO_AGG:
        closer = None
        out_spec = pl.BlockSpec((1, bs, bs), lambda b, rows, cols: (b, 0, 0))
        out_shape = jax.ShapeDtypeStruct((nb, bs, bs), dtype)
    else:
        raise NotImplementedError(f"pallas outer variant {variant}")

    mm_nid = _outer_mm_nid(cplan)

    def kernel(rows, cols, *refs):
        if variant == RIGHT_MM:
            *ins, cls, _zeros, out = refs
        else:
            *ins, out = refs
            cls = None
        xb = ins[0][0]                       # (bs, bs)
        ub = ins[1][...]                     # (bs, r)
        vb = ins[2][...]                     # (bs, r)

        def read(nid: int):
            if nid == cplan.main.nid:
                return xb
            return ins[nid_to_pos[nid]][...]

        vals: dict[int, jnp.ndarray] = {}
        for (nid, op, ins_k, _shape, attrs) in cplan.prog:
            if nid == mm_nid:
                vals[nid] = jax.lax.dot_general(
                    ub, vb, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32).astype(dtype)
                continue
            argv = [vals[ref_] if kind == "n" else
                    (read(ref_) if kind == "b" else ref_)
                    for kind, ref_ in ins_k]
            vals[nid] = ref.eval_node(op, argv, dict(attrs))
        chain = (vals[cplan.prog_root] if cplan.prog_root in vals
                 else read(cplan.prog_root))

        b = pl.program_id(0)
        if variant == FULL_AGG:
            part = jnp.sum(chain).reshape(1, 1).astype(dtype)
            first = b == 0

            @pl.when(first)
            def _():
                out[...] = part

            @pl.when(jnp.logical_not(first))
            def _():
                out[...] = out[...] + part
        elif variant == NO_AGG:
            out[0] = chain.astype(dtype)
        else:                                 # RIGHT_MM
            contrib = jax.lax.dot_general(
                chain, cls[...], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32).astype(dtype)
            prev = rows[jnp.maximum(b - 1, 0)]
            first = jnp.logical_or(b == 0, rows[b] != prev)

            @pl.when(first)
            def _():
                out[...] = contrib

            @pl.when(jnp.logical_not(first))
            def _():
                out[...] = out[...] + contrib

    from jax.experimental.pallas import tpu as pltpu
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(nb,), in_specs=in_specs,
        out_specs=out_spec)
    args = [X.data, _dense(fu), _dense(fv)] + sides
    aliases = {}
    if variant == RIGHT_MM:
        args += [closer, jnp.zeros((m, k_out), dtype)]
        aliases = {len(args) + 1: 0}      # +2 scalar-prefetch operands
    out = pl.pallas_call(kernel, grid_spec=grid_spec, out_shape=out_shape,
                         input_output_aliases=aliases,
                         interpret=interpret,
                         name=obs.kernel_name("outer", variant,
                                              cplan.cache_key()))(
        X.rows, X.cols, *args)
    if variant == NO_AGG:
        return BCSR(out, X.rows, X.cols, X.shape, bs)
    return out


def _bind(cplan: CPlan, env, kind: str):
    for b in cplan.binds:
        if b.kind == kind:
            return _dense(env[b.nid])
    raise KeyError(kind)


def _dense(v):
    return v.todense() if hasattr(v, "todense") else jnp.asarray(v)


def _outer_mm_nid(cplan: CPlan):
    for (nid, op, _ins, _shape, attrs) in cplan.prog:
        if op == "matmul":
            return nid
    return -1
