"""Pallas TPU skeleton for the **Row** template.

SystemML's SpoofRowwise walks one row at a time with a ring buffer of row
intermediates; on TPU the skeleton processes (bm × n) row *panels* resident
in VMEM — row intermediates become panel registers, matvec chains become
panel @ side MXU ops, and the ``col_t_agg`` close (Xᵀ·chain, the MLogreg
pattern) accumulates a full (k×n') output block across the grid.

Binding rules: the main input tiles as (bm, n); side inputs with m rows ride
as (bm, k) panels; anything else (v, W, row vectors) stays fully resident.

**Two orientations.**  The row-major orientation above puts the rows on
sublanes.  The lane-major orientation (``lanes=True``) puts them on
lanes: the kernel reads the main's transpose, an (n, m) array, in
(n, tm) blocks, every m-row side input and the ``no_agg``/``row_agg``
output as its (c, m) transpose in (c, tm) blocks, and runs the program on
the transposed values (``panel @ W`` becomes ``Wᵀ @ panelᵀ``, or a
sublane reduce for one column; row and column reductions swap axes;
a ``col_t_agg`` close over one column is an f32 lane reduce, wider
closes an MXU product).  For an array the device stores column-major, as a TPU stores an
(m, 784) or (m, 1) f32 array, each transpose is a bitcast, so the kernel
reads the array's own bytes: no relayout copy, no lane padding of 784
to 896 or of 1 to 128.  Resident inputs stay whole, in the form the
program reads them in (:func:`lane_forms`).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import obs
from repro.core.cplan import (CPlan, COL_AGG, COL_T_AGG, FULL_AGG, NO_AGG,
                              ROW_AGG)
from . import ref
from .cellwise import (LANE, SUBLANE, VMEM_BLOCK_BUDGET, pick_block,
                       vmem_bytes, _COMB)

#: row-panel target of the Row skeleton (a panel holds whole rows)
ROW_TILE = 128
#: row-block target of the lane-major orientation; the VMEM budget cuts
#: it further (at 784 columns to 1024 rows)
LANE_ROWS = 8192


def row_blocks(cplan: CPlan, shapes: dict, lanes: bool = False):
    """(bm, block shapes of every input then the output) of the Row
    skeleton at these operand shapes (nid -> (rows, cols)).  Side inputs
    with the main's m rows ride along as (bm, c) panels; anything else
    stays fully resident.  ``lanes``: the lane-major orientation's
    (tm, blocks), see :func:`_lane_blocks`."""
    if lanes:
        return _lane_blocks(cplan, shapes)
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
               interpret: bool = False, lanes: bool = False) -> jnp.ndarray:
    """The Row kernel over ``env`` (nid -> dense array).  ``lanes``
    selects the lane-major orientation; the caller checks
    :func:`lane_forms` first."""
    if lanes:
        return _row_pallas_lanes(cplan, env, interpret=interpret)
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
        _accumulate(out, part, variant, agg)

    out = pl.pallas_call(
        kernel, grid=(m // bm,), in_specs=in_specs, out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct(out_shape, dtype),
        interpret=interpret,
        name=obs.kernel_name("row", variant, cplan.cache_key()))(*arrays)
    return _finish_mean(cplan, out)


def _accumulate(out, part, variant: str, agg: str) -> None:
    """Write the first grid step's aggregate, combine every later one."""
    first = pl.program_id(0) == 0

    @pl.when(first)
    def _init():
        out[...] = part

    @pl.when(jnp.logical_not(first))
    def _acc():
        comb = jnp.add if variant == COL_T_AGG else _COMB[agg]
        out[...] = comb(out[...], part)


def _finish_mean(cplan: CPlan, out):
    if (cplan.agg_op or "sum") == "mean" \
            and cplan.variant in (ROW_AGG, COL_AGG, FULL_AGG):
        rr, rc = _root_shape(cplan)
        count = {ROW_AGG: rc, COL_AGG: rr, FULL_AGG: rr * rc}[cplan.variant]
        out = out / count
    return out


# --------------------------------------------------------------------------
# lane-major orientation
# --------------------------------------------------------------------------

def _rows_of(cplan: CPlan):
    """Predicate: does this bound input or program node have the main's
    m rows (a panel, tiled along the grid) rather than stay resident?"""
    m = cplan.main.shape[0]
    rows = {b.nid: b.shape[0] for b in cplan.binds}
    rows.update({nid: shape[0] for (nid, _o, _i, shape, _a) in cplan.prog})
    return lambda nid: m > 1 and rows.get(nid) == m


def _mm_form(tb: bool, column: bool) -> str:
    """Form a resident matmul operand b is read in, for ``panel @ B'``
    with B' = bᵀ if ``tb`` else b: "n" (b as given) or "t" (bᵀ).  The
    single-column reduce (``column``) wants B' itself, (n, 1); the MXU
    product wants B'ᵀ, (k, n)."""
    if column:
        return "t" if tb else "n"
    return "n" if tb else "t"


def lane_forms(cplan: CPlan) -> tuple[dict[int, frozenset], Optional[str]]:
    """(forms each resident bound input is read in, reason), where a form
    is "t" (transposed, the lane-major frame every value lives in) or "n"
    (as given, for a single-column matvec or a transpose in the
    program).  The reason names the first operation of the program that
    has no transposed form, or is None where the whole program has
    one."""
    rows = _rows_of(cplan)
    binds = {b.nid: tuple(b.shape) for b in cplan.binds}
    forms: dict[int, set] = {}

    def need(nid: int, form: str) -> None:
        if nid in binds and not rows(nid):
            forms.setdefault(nid, set()).add(
                "t" if binds[nid] == (1, 1) else form)

    for (nid, op, ins, shape, attrs) in cplan.prog:
        attrs = dict(attrs)
        if op == "matmul":
            (ka, ra), (kb, rb) = ins
            if attrs.get("ta") or ka not in ("n", "b") or not rows(ra) \
                    or kb not in ("n", "b") or rows(rb):
                return {}, (f"matmul %{nid} is not a row panel times a "
                            f"resident operand")
            tb = bool(attrs.get("tb"))
            if ka == "b":
                need(ra, "t")
            if kb == "b":
                need(rb, _mm_form(tb, shape[1] == 1))
            elif tb:
                return {}, (f"matmul %{nid} transposes a resident operand "
                            f"the kernel computes")
            continue
        if op == "t":
            ((k0, r0),) = ins
            if k0 == "b" and not rows(r0):
                need(r0, "n")
                continue
            if tuple(shape) != (1, 1):
                return {}, f"transpose %{nid} of a value the kernel computes"
            continue
        for kind, r in ins:
            if kind == "b":
                need(r, "t")
    roots = [cplan.prog_root]
    if cplan.variant == COL_T_AGG:
        if cplan.close_nid is None or not rows(cplan.close_nid):
            return {}, "col_t_agg closer without the main's rows"
        roots.append(cplan.close_nid)
    for r in roots:
        need(r, "t")
    return {nid: frozenset(f) for nid, f in forms.items()}, None


def _lane_blocks(cplan: CPlan, shapes: dict):
    """(tm, block shapes of every kernel operand then the output) of the
    lane-major orientation.  m-row operands ride as (c, tm) blocks of
    their transposes; residents whole, once per form they are read in.
    tm is the whole row axis where it is at most :data:`LANE_ROWS`, else
    the largest multiple of 128 at most that which divides it, cut until
    the blocks fit the VMEM budget."""
    m = shapes[cplan.main.nid][0]
    forms = lane_forms(cplan)[0]

    def blocks(tm: int) -> list:
        out = []
        for b in cplan.binds:
            r, c = shapes[b.nid]
            if b.nid == cplan.main.nid or (r == m and m > 1):
                out.append((c, tm))
                continue
            for form in sorted(forms.get(b.nid, ("t",))):
                out.append((c, r) if form == "t" else (r, c))
        oc = cplan.out_shape[1]
        out.append({NO_AGG: (oc, tm), ROW_AGG: (1, tm), COL_AGG: (oc, 1),
                    FULL_AGG: (1, 1),
                    COL_T_AGG: tuple(cplan.out_shape)}[cplan.variant])
        return out

    cands = [m] if m <= LANE_ROWS else []
    cands += [t for t in range(min(m - 1, LANE_ROWS) // LANE * LANE, 0,
                               -LANE) if m % t == 0]
    for tm in cands:
        blk = blocks(tm)
        if vmem_bytes(blk) <= VMEM_BLOCK_BUDGET:
            return tm, blk
    return m, blocks(m)


def _apply_program_lanes(cplan: CPlan, read, roots) -> list:
    """Interpret the program on transposed values: every value v of the
    row-major program is held as vᵀ.  ``read(nid, form)`` supplies bound
    inputs, m-row ones always transposed.  Only programs for which
    :func:`lane_forms` names no reason are interpreted here."""
    vals: dict[int, jnp.ndarray] = {}

    def arg(kind, r):
        if kind == "n":
            return vals[r]
        return read(r, "t") if kind == "b" else r

    for (nid, op, ins, shape, attrs) in cplan.prog:
        attrs = dict(attrs)
        if op == "matmul":
            (ka, ra), (kb, rb) = ins
            panel = arg(ka, ra)                      # (n, tm)
            tb = bool(attrs.get("tb"))
            if shape[1] == 1 and kb == "b":
                col = read(rb, _mm_form(tb, True))        # (n, 1)
                vals[nid] = jnp.sum(col * panel, axis=0, keepdims=True)
            else:
                w = (read(rb, _mm_form(tb, False)) if kb == "b"
                     else vals[rb])                  # (k, n)
                vals[nid] = jnp.dot(w, panel)
        elif op == "t":
            ((k0, r0),) = ins
            vals[nid] = read(r0, "n") if k0 == "b" else vals[r0]
        elif op == "idx":
            vals[nid] = arg(*ins[0])[attrs["lo"]:attrs["hi"], :]
        elif op in ref._AGG_FN and "axis" in attrs:
            ax = {"full": None, "row": 0, "col": 1}[attrs["axis"]]
            vals[nid] = ref._AGG_FN[op](arg(*ins[0]), axis=ax, keepdims=True)
        else:
            vals[nid] = ref.eval_node(op, [arg(k, r) for k, r in ins], attrs)
    return [vals[r] if r in vals else read(r, "t") for r in roots]


def _row_pallas_lanes(cplan: CPlan, env: dict[int, jnp.ndarray], *,
                      interpret: bool = False) -> jnp.ndarray:
    m = env[cplan.main.nid].shape[0]
    binds = list(cplan.binds)
    shapes = {b.nid: tuple(env[b.nid].shape) for b in binds}
    tm, blocks = _lane_blocks(cplan, shapes)
    forms = lane_forms(cplan)[0]
    variant, agg = cplan.variant, (cplan.agg_op or "sum")
    arrays, in_specs, slot = [], [], {}
    for b in binds:
        a = jnp.asarray(env[b.nid])
        r, c = a.shape
        if b.nid == cplan.main.nid or (r == m and m > 1):
            slot[(b.nid, "t")] = len(arrays)
            arrays.append(a.T)
            in_specs.append(pl.BlockSpec((c, tm), lambda i: (0, i)))
            continue
        for form in sorted(forms.get(b.nid, ("t",))):
            v = a.T if form == "t" else a
            slot[(b.nid, form)] = len(arrays)
            arrays.append(v)
            in_specs.append(pl.BlockSpec(v.shape, lambda i: (0, 0)))
    dtype = arrays[0].dtype

    roots = [cplan.prog_root]
    if cplan.close_nid is not None:
        roots.append(cplan.close_nid)

    out_blk = blocks[-1]
    tiled = variant in (NO_AGG, ROW_AGG)
    out_shape = (out_blk[0], m) if tiled else out_blk
    out_spec = pl.BlockSpec(out_blk, (lambda i: (0, i)) if tiled
                            else (lambda i: (0, 0)))

    def kernel(*refs):
        *ins, out = refs

        def read(nid, form):
            key = (nid, form) if (nid, form) in slot else (nid, "t")
            return ins[slot[key]][...]

        vals = _apply_program_lanes(cplan, read, roots)
        val = vals[0]
        if variant == NO_AGG:
            out[...] = jnp.broadcast_to(val, out.shape).astype(dtype)
            return
        if variant == ROW_AGG:
            out[...] = _panel_reduce(val, agg, axis=0).astype(dtype)
            return
        if variant == COL_T_AGG and val.shape[0] == 1:
            # closerᵀ @ one column: an f32 multiply and a lane reduce,
            # as a single-column product of the program is formed
            part = jnp.sum(vals[1] * val, axis=1,
                           keepdims=True).astype(dtype)
        elif variant == COL_T_AGG:
            part = jax.lax.dot_general(
                vals[1], val, (((1,), (1,)), ((), ()))).astype(dtype)
        elif variant == COL_AGG:
            part = _panel_reduce(val, agg, axis=1).astype(dtype)
        else:  # FULL_AGG
            part = _panel_reduce(val, agg, axis=None).astype(dtype)
        _accumulate(out, part, variant, agg)

    obs.count(obs.ROW_LANES)
    out = pl.pallas_call(
        kernel, grid=(m // tm,), in_specs=in_specs, out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct(out_shape, dtype),
        interpret=interpret,
        name=obs.kernel_name("rowt", variant, cplan.cache_key()))(*arrays)
    if tiled or variant == COL_AGG:
        out = out.T
    return _finish_mean(cplan, out)


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
