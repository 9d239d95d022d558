"""Distributed execution of generated fused operators (``shard_map``).

The distributed variant of a template runs the *same* generated operator
body as the local one — the CPlan program interpreted at trace time into
one fused computation — but over a row shard of its iteration domain,
mapped across the mesh's data/FSDP axes with ``shard_map``.  With
``pallas`` enabled the body lowers through the template skeletons
(:mod:`repro.kernels.cellwise` / ``rowwise`` / ``multiagg`` /
``outerprod``) whose grids and BlockSpecs are derived from the
*shard-local* shapes the ``shard_map`` body sees, so the generated
kernels execute as ``pallas_call`` **inside** the region instead of
falling back to XLA.  What differs per template is only the wiring the
plan's :class:`~repro.core.cost.Placement` prescribes:

* **in_specs** — operands the placement marked ``sharded`` (row-aligned
  with the iteration domain) arrive as ``P(axes, None)`` row panels;
  block-sparse sharded mains arrive as
  :class:`~repro.kernels.blocksparse.ShardedBCSR` (block-row-partitioned
  outside ``jit``, leading axis sharded).  Everything else (side-input
  row vectors, scalars, the narrow matmul operands of Row/Outer
  closures) is broadcast replicated — ``shard_map`` performs the
  all-gather the cost model charged for layout-sharded side inputs.
* **epilogue** — ``"none"`` variants write their own output row panel
  (``out_specs = P(axes, None)``); ``"psum"``/``"pmin"``/``"pmax"``
  variants produce per-shard partials completed by the matching
  ``jax.lax`` collective and replicate the reduced result (multi-
  aggregates ride one ``psum`` of the stacked (k, 1) output).

**Multi-operator bodies**: a plan :class:`~repro.core.select.Segment` —
a maximal run of adjacent distributed-placed operators — lowers to *one*
``shard_map`` region whose body runs every member's generated program in
order over the local row panels.  A row-partitioned intermediate
(``"none"`` epilogue) consumed inside the segment simply stays a local
panel: no global materialization, no gather/re-scatter at the operator
boundary.  Reduced intermediates complete their collective inside the
body and flow replicated.  Only segment *outputs* exit the region.

Lowering is split into two stages so every downgrade is an explicit,
observable decision rather than a silent ``None``:

* :func:`plan_segment` runs eagerly at compile time and validates the
  placement against the mesh (realizable axes, divisible shards).  It
  returns a :class:`SegmentPlan`, or a :class:`SegmentFallback` carrying
  the human-readable reason the body must run locally (abstract
  ``LogicalMesh``, axis mismatch, indivisible rows, …).
* :func:`lower_segment` runs at trace time with the actual bound values
  and builds the ``shard_map`` callable — choosing per-operand in_specs
  from the value formats — or returns a :class:`SegmentFallback` when a
  format cannot be sharded (e.g. a sparse intermediate materialized
  under trace, which cannot be re-bucketed by concrete row index).

Callers record every ``SegmentFallback`` in the compiled plan's fallback
log (surfaced through ``explain()['execution']['fallbacks']``, checked
by the EXE005 verifier invariant and ``fusionlint --strict``); local
execution remains numerically identical by construction, since the
epilogue collectives are exact.
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Callable, Optional

import jax

from repro import faults
from repro.core.cplan import CPlan, NO_AGG
from repro.core.partitions import PlanInvariantError
from . import ops as kops
from .blocksparse import BCSR, DictCompressed, ShardedBCSR, \
    partition_block_rows

faults.register_site(
    "dist.segment",
    "distributed segment planning (plan_segment): eager compile-time "
    "validation of a shard_map segment against the mesh",
    kinds=("error", "latency"),
    handler="an injected error degrades to SegmentFallback — the caller "
            "records it via CompiledPlan.record_fallback (EXE005) and "
            "the members run as local fused steps, numerically exact")

#: structural cache of compiled shard_map operators — the distributed
#: analogue of the plan cache: ``jax.jit`` memoizes per function object,
#: so rebuilding the closure every CompiledPlan (e.g. ``fuse_exprs`` in a
#: loop) would retrace+recompile each call.  Keyed by (structural CPlan
#: hash, mesh, epilogue, axes, per-bind shard mask, pallas mode, operand
#: pytree structure) — the mesh and the value formats are part of the
#: key, so one CompiledPlan re-targeted at a different real mesh (or fed
#: a sparse operand where a dense one was compiled) can never be served
#: a stale executable; bounded LRU.
_FN_CACHE: "OrderedDict[tuple, Callable]" = OrderedDict()
_FN_CACHE_MAX = 256
_FN_LOCK = threading.Lock()


def _collective(epilogue: str, axes) -> Optional[Callable]:
    if epilogue == "psum":
        return lambda x: jax.lax.psum(x, axes)
    if epilogue == "pmin":
        return lambda x: jax.lax.pmin(x, axes)
    if epilogue == "pmax":
        return lambda x: jax.lax.pmax(x, axes)
    return None                                    # "none": sharded write


@dataclass(frozen=True)
class SegmentItem:
    """One operator of a shard_map segment body."""
    cplan: CPlan
    placement: object              # repro.core.cost.Placement
    roots: tuple[int, ...]         # output nids (>1: combined multi-agg)
    export: bool                   # value leaves the region?


@dataclass(frozen=True)
class SegmentFallback:
    """An explicit 'this segment runs locally' decision with its reason.

    Replaces the old silent ``return None``: callers record the reason
    in the compiled plan's fallback log so ``explain()`` and
    ``fusionlint --strict`` can prove no downgrade went unexplained."""
    reason: str


@dataclass
class SegmentPlan:
    """Mesh-validated segment metadata, ready to lower at trace time."""
    items: tuple                     # tuple[SegmentItem]
    axes: tuple                      # realized mesh axis names
    n: int                           # shard count
    ext: tuple                       # external bind nids, in order
    ext_shard: dict                  # nid -> row-sharded?
    epilogues: tuple                 # exported items' epilogues
    cache_token: tuple = field(default=(), repr=False)

    def local_shapes(self, k: int) -> dict:
        """Operand shapes (nid -> (rows, cols)) member ``k`` sees inside
        the ``shard_map`` body: row-sharded operands and the row panels
        of earlier ``"none"``-epilogue members arrive shard-local."""
        panels = {nid for nid, sh in self.ext_shard.items() if sh}
        for prev in self.items[:k]:
            if prev.placement.epilogue == "none":
                panels.update(prev.roots)
        return {b.nid: ((b.shape[0] // self.n, b.shape[1])
                        if b.nid in panels else tuple(b.shape))
                for b in self.items[k].cplan.binds}


def _realizable_axes(mesh, placement):
    """(axes, ok): the placement's row-shard axes on this mesh, or ok=False
    when the runtime cannot realize the plan's shard group."""
    from repro.dist.sharding import axis_size
    axes = tuple(a for a in placement.axes if a in mesh.axis_names)
    if not axes or axis_size(mesh, axes) != placement.n:
        return (), False
    return axes, True


def plan_segment(items: list[SegmentItem], mesh):
    """Validate one plan segment (≥1 distributed operators in dependency
    order) against the mesh → :class:`SegmentPlan`, or a
    :class:`SegmentFallback` naming why the body must run locally.

    Raises :class:`~repro.core.partitions.PlanInvariantError` when the
    segment itself is malformed (an operand both sharded and broadcast
    across members), which ``annotate_segments`` never emits."""
    try:
        faults.fault_point("dist.segment")
    except faults.FaultInjected as e:
        return SegmentFallback(f"injected fault: {e}")
    if not items:
        return SegmentFallback("empty segment")
    if not isinstance(mesh, jax.sharding.Mesh):
        return SegmentFallback(
            "abstract mesh (cost-only layout): distributed placement is "
            "costed and reported but the body runs locally")
    axes, ok = _realizable_axes(mesh, items[0].placement)
    if not ok:
        return SegmentFallback(
            f"mesh cannot realize shard axes {items[0].placement.axes!r} "
            f"x {items[0].placement.n} shards")
    n = items[0].placement.n

    produced: set[int] = set()
    ext: list[int] = []
    ext_shard: dict[int, bool] = {}
    for it in items:
        ax_it, ok = _realizable_axes(mesh, it.placement)
        if not ok or ax_it != axes:
            return SegmentFallback(
                f"member shard axes {it.placement.axes!r} diverge from "
                f"segment axes {axes!r}")
        for b in it.cplan.binds:
            if b.nid in produced:
                continue                           # intra-segment edge
            sh = b.nid in it.placement.sharded
            if b.nid in ext_shard:
                if ext_shard[b.nid] != sh:
                    # annotate_segments only groups members with one
                    # consistent view of each external operand, so
                    # reaching this means the plan was corrupted after
                    # selection — fail loudly, not fall back
                    raise PlanInvariantError(
                        f"segment operand %{b.nid} is row-sharded for "
                        f"one member and broadcast for another — "
                        f"inconsistent shard view inside one region")
                continue
            if sh and b.shape[0] % n:
                return SegmentFallback(            # defensive: plan drift
                    f"sharded operand %{b.nid} rows {b.shape[0]} not "
                    f"divisible across {n} shards")
            ext.append(b.nid)
            ext_shard[b.nid] = sh
        produced.update(it.roots)

    if not any(it.export for it in items):
        return SegmentFallback("segment exports no value")
    epilogues = tuple(it.placement.epilogue for it in items if it.export)
    token = (tuple(it.cplan.cache_key() for it in items), mesh, axes,
             tuple(ext), tuple(sorted(ext_shard.items())),
             tuple((it.placement.epilogue, it.export, it.roots)
                   for it in items))
    return SegmentPlan(tuple(items), axes, n, tuple(ext), ext_shard,
                       epilogues, token)


def _replicated_spec(value, P):
    """An in_specs entry replicating ``value``: P() per pytree leaf (a
    plain P() for dense arrays; a matching pytree of P() for sparse
    formats so ``shard_map`` sees one spec per leaf)."""
    leaves, treedef = jax.tree_util.tree_flatten(value)
    if not isinstance(value, (ShardedBCSR, BCSR, DictCompressed)):
        return P()
    return jax.tree_util.tree_unflatten(treedef, [P()] * len(leaves))


def lower_segment(sp: SegmentPlan, mesh, values=None, *,
                  pallas: str = "never"):
    """Build the ``shard_map`` callable for a validated segment, choosing
    per-operand in_specs from the actual bound value formats (``values``
    None = all dense).  Returns the *unjitted* callable taking the
    external bind values in ``sp.ext`` order, or a
    :class:`SegmentFallback` when a value format cannot be sharded (the
    caller records the reason and runs the members locally — numerically
    identical, collectives are exact)."""
    from jax.sharding import PartitionSpec as P

    axes = sp.axes
    if values is None:
        values = [None] * len(sp.ext)
    in_specs = []
    for nid, v in zip(sp.ext, values):
        if not sp.ext_shard[nid]:
            if isinstance(v, ShardedBCSR):
                return SegmentFallback(
                    f"replicated operand %{nid} arrived pre-partitioned")
            in_specs.append(_replicated_spec(v, P))
            continue
        if isinstance(v, ShardedBCSR):
            if v.nparts != sp.n:
                return SegmentFallback(
                    f"sparse operand %{nid} partitioned into {v.nparts} "
                    f"shards but the mesh has {sp.n}")
            in_specs.append(jax.tree_util.tree_unflatten(
                jax.tree_util.tree_structure(v),
                [P(axes, *([None] * (leaf.ndim - 1)))
                 for leaf in jax.tree_util.tree_leaves(v)]))
        elif isinstance(v, (BCSR, DictCompressed)):
            return SegmentFallback(
                f"row-sharded operand %{nid} is "
                f"{type(v).__name__} under trace: block partitioning "
                f"needs concrete row indices (outside jit)")
        else:
            in_specs.append(P(axes, None))

    # a sparse-main no_agg export would have to re-assemble a global
    # BCSR across the region boundary — not representable as out_specs
    for it in sp.items:
        if not it.export or it.cplan.variant != NO_AGG:
            continue
        mv = values[sp.ext.index(it.cplan.main.nid)] \
            if it.cplan.main.nid in sp.ext else None
        if isinstance(mv, ShardedBCSR) and it.cplan.main.exploit:
            return SegmentFallback(
                f"sparse no_agg output of %{it.roots[0]} cannot cross "
                f"the shard_map boundary")

    out_specs = tuple(P(axes, None) if it.placement.epilogue == "none"
                      else P() for it in sp.items if it.export)
    steps = [(it.cplan, [b.nid for b in it.cplan.binds],
              _collective(it.placement.epilogue, axes), it.roots,
              it.export) for it in sp.items]

    def body(*arrs):
        # each member's generated operator body on the local row panels;
        # intra-segment "none" outputs stay local panels.  Sharded BCSR
        # mains arrive as one-shard ShardedBCSR — squeeze to the local
        # block list; the template lowerings then derive their grids and
        # BlockSpecs from these shard-local shapes.
        env = {nid: (v.local_bcsr() if isinstance(v, ShardedBCSR) else v)
               for nid, v in zip(sp.ext, arrs)}
        outs = []
        for cplan, nids, reduce_fn, roots, export in steps:
            out = kops.execute(cplan, {nid: env[nid] for nid in nids},
                               pallas=pallas)
            if reduce_fn is not None:
                out = reduce_fn(out)
            if len(roots) > 1:                     # combined multi-agg
                for k, r in enumerate(roots):
                    env[r] = out[k].reshape(1, 1)
            else:
                env[roots[0]] = out
            if export:
                outs.append(out)
        return tuple(outs)

    return jax.shard_map(body, mesh=mesh, in_specs=tuple(in_specs),
                         out_specs=out_specs, check_vma=False)


def prepare_segment_values(sp: SegmentPlan, values):
    """Partition concrete row-sharded BCSR operands into
    :class:`ShardedBCSR` (must run *outside* jit — re-bucketing needs
    concrete block-row indices).  Returns ``(prepared, fallback)``;
    ``fallback`` is a :class:`SegmentFallback` when a sparse operand
    cannot be partitioned (tracer or indivisible block rows), in which
    case ``prepared`` is the original values for local execution."""
    prepared = list(values)
    for i, (nid, v) in enumerate(zip(sp.ext, values)):
        if not sp.ext_shard[nid] or not isinstance(v, BCSR):
            continue
        part = partition_block_rows(v, sp.n)
        if part is None:
            return list(values), SegmentFallback(
                f"sparse operand %{nid}: {v.shape[0] // v.bs} block rows "
                f"not partitionable across {sp.n} shards")
        prepared[i] = part
    return prepared, None


def run_segment_local(sp: SegmentPlan, values, *, pallas: str = "never"):
    """Execute the segment's members locally on global values (the
    recorded-fallback path): same programs, no collectives needed since
    every value is whole.  Returns exported outputs in item order."""
    env = {nid: (v.unshard() if isinstance(v, ShardedBCSR) else v)
           for nid, v in zip(sp.ext, values)}
    outs = []
    for it in sp.items:
        out = kops.execute(
            it.cplan, {b.nid: env[b.nid] for b in it.cplan.binds},
            pallas=pallas)
        if len(it.roots) > 1:
            for k, r in enumerate(it.roots):
                env[r] = out[k].reshape(1, 1)
        else:
            env[it.roots[0]] = out
        if it.export:
            outs.append(out)
    return tuple(outs)


def build_segment_fn(items: list[SegmentItem], mesh, *,
                     pallas: str = "never", values=None):
    """Plan + lower in one eager step for callers holding concrete (or
    all-dense) values.  Returns ``(fn, ext_nids, epilogues)`` or a
    :class:`SegmentFallback` naming why the body must run locally."""
    sp = plan_segment(items, mesh)
    if isinstance(sp, SegmentFallback):
        return sp
    fn = lower_segment(sp, mesh, values, pallas=pallas)
    if isinstance(fn, SegmentFallback):
        return fn
    return fn, sp.ext, sp.epilogues


def build_dist_fn(cplan: CPlan, mesh, placement, *, pallas: str = "never",
                  values=None):
    """Compile one distributed fused operator for the per-operator
    dispatch path.  Returns ``(fn, None)`` with the jitted callable —
    taking the *prepared* bound values in ``cplan.binds`` order — or
    ``(None, SegmentFallback)`` naming why the placement cannot execute
    distributed here (the caller records the reason and runs the local
    generated operator; whole-plan staged execution lowers runs of
    adjacent distributed operators through :func:`plan_segment` /
    :func:`lower_segment` instead)."""
    roots = getattr(cplan, "roots", None) or (cplan.prog_root,)
    sp = plan_segment(
        [SegmentItem(cplan, placement, tuple(roots), True)], mesh)
    if isinstance(sp, SegmentFallback):
        return None, sp
    if values is None:
        values = [None] * len(sp.ext)
    prepared, fb = prepare_segment_values(sp, values)
    if fb is not None:
        return None, fb

    # structural hit: a re-traced or structurally-equal plan reuses the
    # jitted shard_map operator (binding is positional, like GeneratedOp)
    shard_mask = tuple(b.nid in placement.sharded for b in cplan.binds)
    fmt = jax.tree_util.tree_structure(tuple(prepared))
    key = (cplan.cache_key(), mesh, placement.epilogue, sp.axes,
           shard_mask, pallas, fmt)
    with _FN_LOCK:
        hit = _FN_CACHE.get(key)
        if hit is not None:
            _FN_CACHE.move_to_end(key)
            return (hit, prepared), None

    seg_fn = lower_segment(sp, mesh, prepared, pallas=pallas)
    if isinstance(seg_fn, SegmentFallback):
        return None, seg_fn
    assert sp.ext == tuple(b.nid for b in cplan.binds)
    fn = jax.jit(lambda *vals: seg_fn(*vals)[0])
    with _FN_LOCK:
        _FN_CACHE[key] = fn
        while len(_FN_CACHE) > _FN_CACHE_MAX:
            _FN_CACHE.popitem(last=False)
    return (fn, prepared), None
