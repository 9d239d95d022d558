"""Pallas TPU skeleton for the **Row** template.

SystemML's SpoofRowwise walks one row at a time with a ring buffer of row
intermediates; on TPU the skeleton processes (bm × n) row *panels* resident
in VMEM — row intermediates become panel registers, matvec chains become
panel @ side MXU ops, and the ``col_t_agg`` close (Xᵀ·chain, the MLogreg
pattern) accumulates a full (k×n') output block across the grid.

Binding rules: the main input tiles as (bm, n); side inputs with m rows ride
as (bm, k) panels; anything else (v, W, row vectors) stays fully resident.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.cplan import (CPlan, COL_AGG, COL_T_AGG, FULL_AGG, NO_AGG,
                              ROW_AGG)
from . import ref
from .cellwise import SUBLANE, pick_block, _COMB

#: row-panel target of the Row skeleton (a panel holds whole rows)
ROW_TILE = 128


def row_blocks(cplan: CPlan, shapes: dict):
    """(bm, block shapes of every input then the output) of the Row
    skeleton at these operand shapes (nid -> (rows, cols)).  Side inputs
    with the main's m rows ride along as (bm, c) panels; anything else
    stays fully resident."""
    m, n = shapes[cplan.main.nid]
    bm = pick_block(m, ROW_TILE, SUBLANE)
    ins = []
    for b in cplan.binds:
        r, c = shapes[b.nid]
        ins.append((bm, n) if b.nid == cplan.main.nid
                   else (bm if r == m and m > 1 else r, c))
    v = cplan.variant
    out = {NO_AGG: (bm, cplan.out_shape[1]), ROW_AGG: (bm, 1),
           COL_AGG: (1, cplan.out_shape[1]), FULL_AGG: (1, 1),
           COL_T_AGG: tuple(cplan.out_shape)}[v]
    return bm, ins + [out]


def row_pallas(cplan: CPlan, env: dict[int, jnp.ndarray], *,
               interpret: bool = False) -> jnp.ndarray:
    m = env[cplan.main.nid].shape[0]
    binds = list(cplan.binds)
    arrays = [jnp.asarray(env[b.nid]) for b in binds]
    bm, blocks = row_blocks(cplan, {b.nid: a.shape
                                    for b, a in zip(binds, arrays)})
    variant, agg = cplan.variant, (cplan.agg_op or "sum")
    dtype = arrays[0].dtype
    in_specs = [pl.BlockSpec(blk, (lambda i: (i, 0)) if blk[0] == bm
                             and a.shape[0] == m else (lambda i: (0, 0)))
                for a, blk in zip(arrays, blocks)]
    nid_to_pos = {b.nid: i for i, b in enumerate(binds)}

    roots = [cplan.prog_root]
    if cplan.close_nid is not None:
        roots.append(cplan.close_nid)

    out_blk = blocks[-1]
    out_shape = (m, out_blk[1]) if variant in (NO_AGG, ROW_AGG) else out_blk
    out_spec = pl.BlockSpec(out_blk, (lambda i: (i, 0))
                            if variant in (NO_AGG, ROW_AGG)
                            else (lambda i: (0, 0)))

    def kernel(*refs):
        *ins, out = refs
        read = lambda nid: ins[nid_to_pos[nid]][...]
        vals = ref.apply_program(cplan, read, roots)
        val = vals[0]
        if variant == NO_AGG:
            out[...] = val.astype(dtype)
            return
        if variant == ROW_AGG:
            out[...] = _panel_reduce(val, agg, axis=1).astype(dtype)
            return
        if variant == COL_T_AGG:
            closer = vals[1]
            part = (closer.T @ val).astype(dtype)
        elif variant == COL_AGG:
            part = _panel_reduce(val, agg, axis=0).astype(dtype)
        else:  # FULL_AGG
            part = _panel_reduce(val, agg, axis=None).astype(dtype)
        first = pl.program_id(0) == 0

        @pl.when(first)
        def _init():
            out[...] = part

        @pl.when(jnp.logical_not(first))
        def _acc():
            comb = jnp.add if variant == COL_T_AGG else _COMB[agg]
            out[...] = comb(out[...], part)

    out = pl.pallas_call(
        kernel, grid=(m // bm,), in_specs=in_specs, out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct(out_shape, dtype),
        interpret=interpret)(*arrays)
    if agg == "mean" and variant in (ROW_AGG, COL_AGG, FULL_AGG):
        rr, rc = _root_shape(cplan)
        count = {ROW_AGG: rc, COL_AGG: rr, FULL_AGG: rr * rc}[variant]
        out = out / count
    return out


def _root_shape(cplan: CPlan) -> tuple[int, int]:
    for (nid, _op, _ins, shape, _attrs) in cplan.prog:
        if nid == cplan.prog_root:
            return shape
    for b in cplan.binds:
        if b.nid == cplan.prog_root:
            return b.shape
    return cplan.main.shape


def _panel_reduce(val, agg: str, axis):
    fn = {"sum": jnp.sum, "mean": jnp.sum, "min": jnp.min,
          "max": jnp.max}[agg]
    return fn(val, axis=axis, keepdims=True)
