"""Pallas TPU skeleton for the **Cell** template (and single-output MAgg).

Hardware adaptation of SystemML's SpoofCellwise: instead of a value-at-a-
time virtual ``genexec``, the skeleton is a 2-D grid over MXU/VPU-aligned
VMEM tiles; the generated operator (the CPlan program) is interpreted at
trace time on tile values, emitting one fused kernel.  Aggregation variants
accumulate across the reduction grid axis, which is laid out innermost so
the output block stays resident in VMEM.

Broadcast binding: (m,n) matrices tile as (bm,bn); (m,1)/(1,n) vectors ride
along as (bm,1)/(1,bn) tiles; scalars as (1,1).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.cplan import (CPlan, COL_AGG, FULL_AGG, NO_AGG, ROW_AGG)
from . import ref


#: Mosaic tiles the last two dims of every block by (8, 128) for f32
#: (sublanes x lanes): each block dim is a multiple of its quantum or the
#: array's full dimension, or the kernel does not lower.
SUBLANE, LANE = 8, 128
#: tile targets (rows, cols) of the Cell and MAgg skeletons
CELL_TILE = (256, 512)


def pick_block(dim: int, target: int, quantum: int) -> int:
    """Block length along one axis of length ``dim``: the whole axis when
    it fits ``target``, else the largest multiple of ``quantum`` that is
    at most ``target`` and divides ``dim``, else the whole axis — the only
    other legal block (:func:`vmem_bytes` then decides whether it fits).
    Divisibility keeps every tile full, so the kernels need no masking."""
    if dim <= target:
        return dim
    for b in range(target - target % quantum, 0, -quantum):
        if dim % b == 0:
            return b
    return dim


def vmem_bytes(blocks, itemsize: int = 4) -> int:
    """Scoped VMEM the pipelined blocks of one kernel occupy: two buffers
    per block (the pipeline prefetches the next while computing the
    current), each padded up to whole (8, 128) tiles."""
    pad = lambda d, q: -(-d // q) * q
    return sum(2 * pad(r, SUBLANE) * pad(c, LANE) * itemsize
               for r, c in blocks)


def _block_shape(shape, bm: int, bn: int) -> tuple[int, int]:
    """Block of a broadcast-compatible operand under (bm, bn) tiling of
    the main input (mirrors :func:`_tile_spec`)."""
    r, c = shape
    return (1 if r == 1 else bm, 1 if c == 1 else bn)


def cell_blocks(cplan: CPlan, shapes: dict):
    """(bm, bn, block shapes of every input then the output) of the Cell
    and MAgg skeletons at these operand shapes (nid -> (rows, cols))."""
    m, n = shapes[cplan.main.nid]
    bm = pick_block(m, CELL_TILE[0], SUBLANE)
    bn = pick_block(n, CELL_TILE[1], LANE)
    ins = [_block_shape(shapes[b.nid], bm, bn) for b in cplan.binds]
    if cplan.extra:
        out = (1 + len(cplan.extra), 1)
    else:
        out = {NO_AGG: (bm, bn), ROW_AGG: (bm, 1), COL_AGG: (1, bn),
               FULL_AGG: (1, 1)}[cplan.variant]
    return bm, bn, ins + [out]


def _tile_spec(shape, m, n, bm, bn, reduce_over_rows: bool):
    """BlockSpec for a broadcast-compatible input of ``shape``; grid is
    (outer, inner) where inner is the reduction axis."""
    r, c = shape
    if reduce_over_rows:     # grid = (n/bn, m/bm): o=col tile, i=row tile
        ix_m, ix_n = (lambda o, i: i), (lambda o, i: o)
    else:                    # grid = (m/bm, n/bn)
        ix_m, ix_n = (lambda o, i: o), (lambda o, i: i)
    if (r, c) == (1, 1):
        return pl.BlockSpec((1, 1), lambda o, i: (0, 0))
    if r == 1:
        return pl.BlockSpec((1, bn), lambda o, i: (0, ix_n(o, i)))
    if c == 1:
        return pl.BlockSpec((bm, 1), lambda o, i: (ix_m(o, i), 0))
    return pl.BlockSpec((bm, bn), lambda o, i: (ix_m(o, i), ix_n(o, i)))


_INIT = {"sum": 0.0, "min": jnp.inf, "max": -jnp.inf, "mean": 0.0}
_COMB = {"sum": jnp.add, "min": jnp.minimum, "max": jnp.maximum,
         "mean": jnp.add}


def cell_pallas(cplan: CPlan, env: dict[int, jnp.ndarray], *,
                interpret: bool = False) -> jnp.ndarray:
    main = env[cplan.main.nid]
    m, n = main.shape
    bm, bn, _ = cell_blocks(
        cplan, {b.nid: env[b.nid].shape for b in cplan.binds})
    variant, agg = cplan.variant, (cplan.agg_op or "sum")
    reduce_rows = variant == COL_AGG      # reduce over m → rows innermost

    binds = [b for b in cplan.binds]
    arrays = [jnp.asarray(env[b.nid]) for b in binds]
    dtype = arrays[0].dtype
    in_specs = [_tile_spec(a.shape, m, n, bm, bn, reduce_rows)
                for a in arrays]
    nid_to_pos = {b.nid: i for i, b in enumerate(binds)}

    if variant == NO_AGG:
        grid = (m // bm, n // bn)
        out_spec = pl.BlockSpec((bm, bn), lambda o, i: (o, i))
        out_shape = (m, n)
    elif variant == ROW_AGG:
        grid = (m // bm, n // bn)
        out_spec = pl.BlockSpec((bm, 1), lambda o, i: (o, 0))
        out_shape = (m, 1)
    elif variant == COL_AGG:
        grid = (n // bn, m // bm)
        out_spec = pl.BlockSpec((1, bn), lambda o, i: (0, o))
        out_shape = (1, n)
    elif variant == FULL_AGG:
        grid = (m // bm, n // bn)
        out_spec = pl.BlockSpec((1, 1), lambda o, i: (0, 0))
        out_shape = (1, 1)
    else:
        raise NotImplementedError(variant)

    def kernel(*refs):
        *ins, out = refs
        read = lambda nid: ins[nid_to_pos[nid]][...]
        (val,) = ref.apply_program(cplan, read, [cplan.prog_root])
        if variant == NO_AGG:
            out[...] = val.astype(dtype)
            return
        if variant == ROW_AGG:
            part = _reduce(val, agg, axis=1)
        elif variant == COL_AGG:
            part = _reduce(val, agg, axis=0)
        else:
            part = _reduce(val, agg, axis=None)
        part = part.astype(dtype)
        i = pl.program_id(1)
        first = i == 0
        if variant == FULL_AGG:
            first = jnp.logical_and(pl.program_id(0) == 0, first)

        @pl.when(first)
        def _init():
            out[...] = part

        @pl.when(jnp.logical_not(first))
        def _acc():
            out[...] = _COMB[agg](out[...], part)

    out = pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct(out_shape, dtype),
        interpret=interpret)(*arrays)
    if agg == "mean":
        count = {ROW_AGG: n, COL_AGG: m, FULL_AGG: m * n}.get(variant, 1)
        out = out / count
    return out


def _reduce(val, agg: str, axis):
    fn = {"sum": jnp.sum, "mean": jnp.sum,
          "min": jnp.min, "max": jnp.max}[agg]
    return fn(val, axis=axis, keepdims=True)
