"""Code generation & runtime integration (paper §2.1-2.2).

Turns selected plans into executable operators and whole ExecPlans into
callables.  Two cache layers memoize the generated code:

* the **plan cache** memoizes generated *operators* by structural CPlan
  hash (shapes/ops/binding/variant) so dynamic recompilation and repeated
  tracing reuse compiled operators — the paper's Fig. 11 mechanism;
* the **whole-plan cache** memoizes the *staged plan function* — the
  entire ExecPlan (fused operators, basic ops, literals, multi-aggregate
  unpacking, distributed segments) traced into one function and jitted
  once — by structural plan signature, so structurally-equal plans share
  one XLA executable.

Staged execution is the default for **every** operand format and Pallas
mode — dense, BCSR, CLA-compressed, ``pallas="interpret"`` — one
dispatch per plan call, literals folded as trace constants, dead
intermediates released via ``_last_uses`` (XLA then reuses their
buffers — plan-level buffer donation), and runs of adjacent distributed
operators lowered into a single ``shard_map`` region whose body runs the
generated kernels over shard-local shapes
(:mod:`repro.kernels.distributed`).  Only ``compile_plan(staged=False)``
selects the per-operator interpreter dispatch, kept as an explicit debug
path.  Any remaining downgrade (e.g. a sparse operand whose block rows
do not partition across the mesh) is *recorded*, never silent: the
reasons surface in ``explain()['execution']['fallbacks']``, are checked
by the EXE005 verifier invariant and by ``fusionlint --strict``, and
raise under ``FusionContext(verify="strict")`` when a costed distributed
placement is abandoned at execution time.

One walk over the plan, :func:`plan_steps`, decides how an ExecPlan
becomes staged steps: which specs run as ``shard_map`` segments, which
as local fused kernels, which take their XLA body instead of a Pallas
kernel, which local Row kernels may run lane-major, and the structural
whole-plan key.  The lowering (:class:`CompiledPlan`),
:func:`staged_plan_key`, :func:`plan_fallbacks` and
:func:`row_orientations` all read its :class:`StepTable`.

Execution paths per operator are chosen by the dispatcher in
``kernels/ops.py`` (dense XLA, dense Pallas, BCSR sparsity-exploiting,
CLA-compressed); the full kernel-dispatch decision table lives in
``docs/architecture.md``.
"""

from __future__ import annotations

import functools
import threading
from collections import OrderedDict
from dataclasses import dataclass, field, replace
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Layout

from repro import faults, obs
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.kernels.blocksparse import (BCSR, DictCompressed, ShardedBCSR)
from repro.kernels.rowwise import lane_forms
from .cost import FusedOpSpec
from .cplan import CPlan, NO_AGG, build_cplan
from .ir import Graph, Node
from .partitions import PlanInvariantError
from .select import ExecPlan, MultiAggSpec
from .templates import TType


faults.register_site(
    "plan.jit_build",
    "whole-plan XLA build: jit(plan_fn) / jit(vmap(plan_fn)) inside the "
    "whole-plan cache builder (first call per structural plan key)",
    kinds=("error", "latency"),
    handler="FusionServer._entry build ladder (batched → exact-shape → "
            "per-op) + build circuit breaker; failed builds are not "
            "cached, so retries rebuild")


def _mesh_of(layout):
    """Mesh carried by a layout-ish object: a FusionLayout (``.mesh``),
    a bare mesh passed directly (``.axis_names``), or None."""
    if layout is None:
        return None
    mesh = getattr(layout, "mesh", None)
    if mesh is None and hasattr(layout, "axis_names"):
        return layout
    return mesh


def _is_real_mesh(mesh) -> bool:
    """True for an executable jax Mesh (vs an abstract LogicalMesh used
    for cost-only planning, or None)."""
    from jax.sharding import Mesh
    return isinstance(mesh, Mesh)


# --------------------------------------------------------------------------
# plan cache
# --------------------------------------------------------------------------

@dataclass
class PlanCacheStats:
    hits: int = 0
    misses: int = 0
    evictions: int = 0
    size: int = 0
    capacity: int = 0
    codegen_time_s: float = 0.0

    @property
    def total(self) -> int:
        return self.hits + self.misses


class PlanCache:
    """Thread-safe LRU cache of generated operators keyed by structural
    CPlan hash.  Bounded: least-recently-used operators are evicted past
    ``maxsize`` (XLA still holds its own executable cache; this bounds the
    python-side operator objects).  The bound is configurable — pass
    ``maxsize``, set ``REPRO_PLAN_CACHE_CAPACITY`` in the environment, or
    call :meth:`resize` on a live cache (long-lived serving processes
    churn through thousands of plan structures; unbounded growth is a
    slow leak)."""

    def __init__(self, maxsize: Optional[int] = None) -> None:
        if maxsize is None:
            import os
            maxsize = int(os.environ.get("REPRO_PLAN_CACHE_CAPACITY", 512))
        self.maxsize = int(maxsize)
        self._ops: "OrderedDict[str, GeneratedOp]" = OrderedDict()
        self._lock = threading.RLock()
        self.stats = PlanCacheStats(capacity=self.maxsize)

    def resize(self, maxsize: int) -> None:
        """Change the LRU capacity, evicting LRU entries past the new
        bound immediately."""
        with self._lock:
            self.maxsize = int(maxsize)
            self.stats.capacity = self.maxsize
            while len(self._ops) > self.maxsize:
                self._ops.popitem(last=False)
                self.stats.evictions += 1
            self.stats.size = len(self._ops)

    def get_or_build(self, graph: Graph, spec) -> tuple["GeneratedOp", "CPlan"]:
        """Returns (generated operator, this spec's CPlan).  The operator
        may come from a structurally-equal plan of a *different* graph, so
        callers bind inputs positionally via the returned CPlan."""
        with obs.span(obs.CODEGEN) as sp:
            cplan = build_cplan(graph, spec)
            key = cplan.cache_key()
            with self._lock:
                hit = self._ops.get(key)
                if hit is not None:
                    self._ops.move_to_end(key)
                    self.stats.hits += 1
                    return hit, cplan
                op = GeneratedOp(cplan)
                self._ops[key] = op
                while len(self._ops) > self.maxsize:
                    self._ops.popitem(last=False)
                    self.stats.evictions += 1
                self.stats.size = len(self._ops)
        with self._lock:
            self.stats.misses += 1
            self.stats.codegen_time_s += sp.seconds
        return op, cplan

    def __len__(self) -> int:
        with self._lock:
            return len(self._ops)

    def clear(self) -> None:
        with self._lock:
            self._ops.clear()
            self.stats = PlanCacheStats(capacity=self.maxsize)


PLAN_CACHE = PlanCache()


def plan_cache_stats() -> PlanCacheStats:
    """Snapshot of the global plan-cache counters (public API).

    Returns a :class:`PlanCacheStats` value (not a live view) with
    ``hits`` / ``misses`` / ``total`` (get-or-build calls), ``evictions``
    (LRU past the configurable ``capacity`` bound — 512 operators by
    default), ``size`` (operators currently cached), ``capacity`` (the
    current LRU bound), and ``codegen_time_s`` (cumulative CPlan-build
    time on misses, from their ``repro.codegen`` spans).  The cache keys
    operators by *structural* CPlan hash, so a hit means some
    structurally-equal plan — any expression, any trace — already
    generated the operator.  Useful assertions:
    ``stats.total`` grows when a backward pass compiles, ``misses`` stays
    flat across re-traces of the same shapes."""
    with PLAN_CACHE._lock:
        return replace(PLAN_CACHE.stats, size=len(PLAN_CACHE._ops),
                       capacity=PLAN_CACHE.maxsize)


# --------------------------------------------------------------------------
# whole-plan cache (staged plan functions, layered on the plan cache)
# --------------------------------------------------------------------------

@dataclass
class WholePlanCacheStats:
    hits: int = 0
    misses: int = 0
    evictions: int = 0
    size: int = 0
    capacity: int = 0
    build_time_s: float = 0.0
    #: per-key stat records currently tracked / dropped past the bound
    tracked_keys: int = 0
    dropped_keys: int = 0

    @property
    def total(self) -> int:
        return self.hits + self.misses


#: per-key stat records kept across entry churn (bounded separately from
#: the function LRU so eviction metrics survive the evicted entries)
KEY_STATS_CAPACITY = 4096


class WholePlanCache:
    """Thread-safe LRU of jitted whole-plan functions keyed by structural
    plan signature (per-operator CPlan hashes + env wiring + literals +
    segment/placement structure + pallas policy + mesh).  A hit returns
    the *same* jitted function object, so XLA's executable cache is shared
    across structurally-equal CompiledPlans (``fuse_exprs`` in a loop,
    re-traced shapes, the backward of an identical forward).

    **Build-once:** :meth:`get_or_create` serializes concurrent misses on
    the same key — one thread builds, the rest wait and share the result —
    so N threads compiling structurally-equal plans produce exactly one
    jitted function (duplicate jit wrappers would each pay their own XLA
    compile later).

    **Lifecycle:** the LRU bound is configurable (``maxsize`` /
    ``REPRO_WHOLE_PLAN_CACHE_CAPACITY`` / :meth:`resize`) and per-key
    hit/miss/eviction/build-time counters (:meth:`key_stats`) survive
    entry eviction, so a serving process churning through thousands of
    plan structures can still report which keys thrash."""

    def __init__(self, maxsize: Optional[int] = None) -> None:
        if maxsize is None:
            import os
            maxsize = int(os.environ.get(
                "REPRO_WHOLE_PLAN_CACHE_CAPACITY", 256))
        self.maxsize = int(maxsize)
        self._fns: "OrderedDict[tuple, Callable]" = OrderedDict()
        self._lock = threading.RLock()
        self._pending: dict[tuple, threading.Event] = {}
        self._key_stats: "OrderedDict[str, dict]" = OrderedDict()
        #: argument signatures each live staged function was called with
        self._called: dict[tuple, set] = {}
        self.stats = WholePlanCacheStats(capacity=self.maxsize)

    # -- per-key metrics ----------------------------------------------------
    @staticmethod
    def key_digest(key: tuple) -> str:
        """Short stable-within-process label for one structural key."""
        return format(hash(key) & 0xFFFFFFFFFFFF, "012x")

    def _key_record(self, key: tuple) -> dict:
        # caller holds the lock
        digest = self.key_digest(key)
        rec = self._key_stats.get(digest)
        if rec is None:
            rec = {"key": digest, "hits": 0, "misses": 0, "evictions": 0,
                   "build_time_s": 0.0}
            self._key_stats[digest] = rec
            while len(self._key_stats) > KEY_STATS_CAPACITY:
                self._key_stats.popitem(last=False)
                self.stats.dropped_keys += 1
        else:
            self._key_stats.move_to_end(digest)
        return rec

    def key_stats(self, top: Optional[int] = None) -> list[dict]:
        """Per-key counter records, most recently touched last; records
        outlive their cache entries (eviction is itself a counter)."""
        with self._lock:
            recs = [dict(r) for r in self._key_stats.values()]
        if top is not None:
            recs = recs[-top:]
        return recs

    # -- LRU ----------------------------------------------------------------
    def resize(self, maxsize: int) -> None:
        """Change the LRU capacity, evicting past the new bound now."""
        with self._lock:
            self.maxsize = int(maxsize)
            self.stats.capacity = self.maxsize
            self._evict_over_capacity()
            self.stats.size = len(self._fns)

    def _evict_over_capacity(self) -> None:
        # caller holds the lock
        while len(self._fns) > self.maxsize:
            old_key, _ = self._fns.popitem(last=False)
            self._called.pop(old_key, None)
            self.stats.evictions += 1
            self._key_record(old_key)["evictions"] += 1

    def get(self, key: tuple) -> Optional[Callable]:
        with self._lock:
            fn = self._fns.get(key)
            if fn is not None:
                self._fns.move_to_end(key)
                self.stats.hits += 1
                self._key_record(key)["hits"] += 1
            return fn

    def put(self, key: tuple, fn: Callable, build_s: float) -> None:
        with self._lock:
            self._fns[key] = fn
            self._called[key] = set()
            self._evict_over_capacity()
            self.stats.misses += 1
            self.stats.size = len(self._fns)
            self.stats.build_time_s += build_s
            rec = self._key_record(key)
            rec["misses"] += 1
            rec["build_time_s"] += build_s

    def get_or_create(self, key: tuple, builder: Callable[[], Callable],
                      extra_build_s: float = 0.0) -> Callable:
        """Hit, or build exactly once under concurrency: the first thread
        to miss a key runs ``builder`` (outside the lock) while racing
        threads block on an in-flight event and then share the built
        function.  ``extra_build_s`` lets the caller account lowering
        work done before the key existed (e.g. tracing the plan body);
        the build itself is timed by its ``repro.codegen`` span."""
        while True:
            with self._lock:
                fn = self._fns.get(key)
                if fn is not None:
                    self._fns.move_to_end(key)
                    self.stats.hits += 1
                    self._key_record(key)["hits"] += 1
                    return fn
                ev = self._pending.get(key)
                if ev is None:
                    ev = threading.Event()
                    self._pending[key] = ev
                    break                      # we own the build
            ev.wait()                          # another thread is building
        try:
            with obs.span(obs.CODEGEN) as sp:
                fn = builder()
            self.put(key, fn, sp.seconds + extra_build_s)
            return fn
        finally:
            with self._lock:
                self._pending.pop(key, None)
            ev.set()

    def first_call(self, key: tuple, args) -> bool:
        """True the first time the live staged function under ``key`` is
        called with arguments of this signature (pytree structure, shapes
        and dtypes): the call in which JAX traces, lowers and compiles
        it.  False for a key no longer in the cache."""
        leaves, tree = jax.tree_util.tree_flatten(args)
        sig = (tree, tuple((getattr(v, "shape", None),
                            getattr(v, "dtype", None)) for v in leaves))
        with self._lock:
            seen = self._called.get(key)
            if seen is None or sig in seen:
                return False
            seen.add(sig)
            return True

    def clear(self) -> None:
        with self._lock:
            self._fns.clear()
            self._called.clear()
            self._key_stats.clear()
            self.stats = WholePlanCacheStats(capacity=self.maxsize)


WHOLE_PLAN_CACHE = WholePlanCache()


def whole_plan_cache_stats() -> WholePlanCacheStats:
    """Snapshot of the whole-plan cache counters (public API): ``hits``
    (a structurally-equal ExecPlan reused an existing staged function —
    and with it the XLA executable), ``misses`` (staged functions built,
    concurrent builders coalesced to one build per key), ``size``,
    ``capacity`` (the configurable LRU bound), ``evictions``,
    ``build_time_s`` (cumulative staged-lowering time on misses, from
    their ``repro.codegen`` spans), and
    ``tracked_keys``/``dropped_keys`` (per-key stat records alive /
    aged out — see :meth:`WholePlanCache.key_stats`)."""
    with WHOLE_PLAN_CACHE._lock:
        return replace(WHOLE_PLAN_CACHE.stats,
                       size=len(WHOLE_PLAN_CACHE._fns),
                       capacity=WHOLE_PLAN_CACHE.maxsize,
                       tracked_keys=len(WHOLE_PLAN_CACHE._key_stats))


# --------------------------------------------------------------------------
# generated operators
# --------------------------------------------------------------------------

@dataclass
class GeneratedOp:
    """A fused operator: CPlan + execution dispatch (SystemML's SpoofOp).

    The program is interpreted at trace time under ``jax.jit`` — the jitted
    computation is the compiled generated operator (the janino-compile
    analogue); jax caches it per input shape/format signature.
    """
    cplan: CPlan
    _jits: dict = field(default_factory=dict)

    def _run(self, env: dict[int, object], pallas: str):
        # format routing (incl. BCSR+Outer → outer_pallas) lives in the
        # kops.execute dispatcher, shared with the staged path
        return kops.execute(self.cplan, env, pallas=pallas)

    def __call__(self, env: dict[int, object], pallas: str = "never"):
        if pallas == "interpret":
            return self._run(env, pallas)     # validation path: stay eager
        fn = self._jits.get(pallas)
        if fn is None:
            fn = jax.jit(lambda e: self._run(e, pallas))
            self._jits[pallas] = fn
        return fn(env)


def _eval_basic(graph: Graph, node: Node, env: dict[int, object]):
    """Basic (unfused) operator, sparse-format aware."""
    ins = [env[i.nid] if i.op != "lit" else
           jnp.asarray(float(i.attrs["value"]), jnp.float32).reshape(1, 1)
           for i in node.inputs]
    # an input partitioned for a shard_map segment but also consumed
    # here re-assembles to its global block list (exact: zero padding)
    ins = [v.unshard() if isinstance(v, ShardedBCSR) else v for v in ins]
    if node.is_matmul and isinstance(ins[0], BCSR):
        b = ins[1]
        b = b.todense() if hasattr(b, "todense") else b
        b = b.T if node.tb else b
        # ta=True: transpose the block structure (BCSR.T is exact and
        # O(nnz)) instead of densifying the sparse operand.
        a = ins[0].T if node.ta else ins[0]
        return kops.bcsr_matmul(a, b)
    if node.op == "mul" and isinstance(ins[0], BCSR) \
            and not isinstance(ins[1], BCSR) \
            and getattr(ins[1], "shape", None) == ins[0].shape:
        return kops.bcsr_mul_dense(ins[0], ins[1])
    ins = [v.todense() if hasattr(v, "todense") else v for v in ins]
    return kref.eval_node(node.op, ins, node.attrs)


# --------------------------------------------------------------------------
# executable plans
# --------------------------------------------------------------------------

def _spec_roots(spec) -> tuple[int, ...]:
    return tuple(spec.roots) if isinstance(spec, MultiAggSpec) \
        else (spec.root,)


def _segment_items(graph: Graph, plan: ExecPlan, seg,
                   cache: PlanCache) -> list:
    """SegmentItems for one plan Segment (:func:`plan_steps`)."""
    from repro.kernels.distributed import SegmentItem
    specs = plan.specs
    output_ids = set(graph.output_ids)
    cons: dict[int, set[int]] = {}
    for j, s in enumerate(specs):
        for i in s.inputs:
            cons.setdefault(i, set()).add(j)
    seg_set = set(seg.indices)
    items = []
    for j in seg.indices:
        spec = specs[j]
        _op, cplan = cache.get_or_build(graph, spec)
        roots = _spec_roots(spec)
        export = any(r in output_ids or (cons.get(r, set()) - seg_set)
                     for r in roots)
        items.append(SegmentItem(cplan, spec.placement, roots, export))
    return items


def _fused(spec) -> bool:
    return isinstance(spec, MultiAggSpec) or (
        isinstance(spec, FusedOpSpec) and spec.fused)


@dataclass(frozen=True)
class StepTable:
    """How an ExecPlan runs as one staged function under one layout and
    Pallas mode.  :func:`plan_steps` makes every one of these decisions;
    the lowering, the whole-plan key, ``explain()``, the verifier and
    ``fusionlint`` read them from here.

    ``steps`` run in order, each one of ``("seg", SegmentPlan, the roots
    of each exported member)``, a ``shard_map`` region; ``("fused",
    cplan, bind nids, roots, main position)``, a local fused operator;
    ``("basic", node)``.  ``key`` is the structural whole-plan key of
    the row-major lowering.  ``fallbacks`` lists each plan-time
    downgrade as (site, spec indices, reason) in step order: a
    ``segment`` or ``operator`` placement the mesh cannot realize, and a
    dense ``kernel`` that takes its XLA body at the shapes its step
    sees.  ``rows`` has (spec index, main position, reason) for each
    local dense Row kernel: the main's input position where each call's
    main decides the orientation (the fused step carries the same
    position), else the reason it stays row-major.  ``free[i]`` are the
    values dead after step ``i``."""
    steps: tuple
    key: tuple
    fallbacks: tuple
    rows: tuple
    free: tuple

    def fallback_report(self, staged: bool = True) -> list:
        """The plan-time part of ``explain()["execution"]["fallbacks"]``:
        segments, then operators outside a segment already reported,
        then kernels."""
        out = [] if staged else [{
            "site": "plan",
            "reason": "staged=False: per-operator debug dispatch requested"}]
        fell = {j for site, specs, _r in self.fallbacks if site == "segment"
                for j in specs}
        for want in ("segment", "operator", "kernel"):
            out.extend({"site": site, "specs": list(specs), "reason": reason}
                       for site, specs, reason in self.fallbacks
                       if site == want and not (site == "operator"
                                                and specs[0] in fell))
        return out

    def orientations(self) -> list:
        """The static ``explain()["execution"]["row_orientation"]``: each
        local dense Row kernel ``row_major`` with its reason, or
        ``by_layout`` where each call's main decides."""
        return [{"specs": [idx], "orientation": "row_major",
                 "reason": reason} if pos is None else
                {"specs": [idx], "orientation": "by_layout"}
                for idx, pos, reason in self.rows]


def plan_steps(plan: ExecPlan, layout=None, pallas: str = "never",
               cache: Optional[PlanCache] = None) -> StepTable:
    """The :class:`StepTable` of ``plan`` under ``layout`` and ``pallas``,
    from one walk over ``plan.specs``, tracing nothing.  Under a mesh a
    plan segment, else a ``distributed`` operator alone, becomes a
    ``shard_map`` step where :func:`~repro.kernels.distributed.
    plan_segment` realizes it; the rest run as local steps.  Every value
    a step consumes resolves to a canonical token, so a ``KeyError``
    here means the plan wires a value no step produces (the verifier's
    EXE004)."""
    from repro.kernels.distributed import (SegmentFallback, SegmentItem,
                                           plan_segment)
    cache = cache if cache is not None else PLAN_CACHE
    graph, specs = plan.graph, plan.specs
    mesh = _mesh_of(layout)
    in_pos = {n.nid: p for p, n in enumerate(graph.inputs())}
    output_ids = tuple(o.nid for o in graph.outputs)

    # canonical env tokens: whole-plan keys must capture the wiring,
    # not the node ids (structurally-equal plans from other traces
    # must hit)
    canon: dict[int, tuple] = {nid: ("in", p) for nid, p in in_pos.items()}
    for n in graph.nodes:
        if n.op == "lit":
            canon[n.nid] = ("lit", float(n.attrs["value"]))

    steps: list[tuple] = []
    key_parts: list[tuple] = []      # structural key, one per step
    spec_step: dict[int, int] = {}   # spec idx -> step idx
    fallbacks: list[tuple] = []
    rows: list[tuple] = []

    def _token(roots: tuple[int, ...], step_idx: int,
               item_idx: int = 0) -> None:
        # the item index distinguishes the members of one segment
        # step — without it two outputs of the same step would be
        # indistinguishable in the whole-plan key and a structurally
        # different consumer wiring could hit the wrong function
        for k, r in enumerate(roots):
            canon[r] = ("s", step_idx, item_idx, k)

    def _kernel(idx: int, cplan: CPlan, shapes: dict) -> Optional[str]:
        # why the operator takes its XLA body at these shapes (reported
        # for a dense main: the planner saw a sparse one run BCSR rows)
        reason = kops.kernel_fallback(cplan, shapes)
        if reason is not None and cplan.main.sparsity >= 1.0:
            fallbacks.append(("kernel", (idx,), reason))
        return reason

    def _seg_step(items: list, sp, indices: tuple) -> None:
        step_idx = len(steps)
        steps.append(("seg", sp, tuple(it.roots for it in items
                                       if it.export)))
        key_parts.append((
            "seg", mesh,
            tuple((it.cplan.cache_key(), it.placement.epilogue,
                   tuple(b.nid in it.placement.sharded
                         for b in it.cplan.binds), it.export)
                  for it in items),
            tuple(canon[nid] for nid in sp.ext)))
        for k, (j, it) in enumerate(zip(indices, items)):
            spec_step[j] = step_idx
            _token(it.roots, step_idx, k)
            if pallas != "never":
                _kernel(j, it.cplan, sp.local_shapes(k))

    def _fused_step(idx: int, cplan: CPlan, roots: tuple) -> None:
        step_idx = len(steps)
        bind_nids = tuple(b.nid for b in cplan.binds)
        key_parts.append(("fused", cplan.cache_key(),
                          tuple(canon[nid] for nid in bind_nids)))
        main_pos = None
        if pallas != "never":
            shapes = {b.nid: tuple(b.shape) for b in cplan.binds}
            if _kernel(idx, cplan, shapes) is None \
                    and cplan.ttype == TType.ROW and not cplan.extra:
                reason = (lane_forms(cplan)[1]
                          or kops.kernel_fallback(cplan, shapes, lanes=True))
                if reason is None and cplan.main.nid not in in_pos:
                    reason = (f"main %{cplan.main.nid} is computed inside "
                              f"the plan: its device layout is not observed")
                if reason is None:
                    main_pos = in_pos[cplan.main.nid]
                rows.append((idx, main_pos, reason))
        steps.append(("fused", cplan, bind_nids, roots, main_pos))
        spec_step[idx] = step_idx
        _token(roots, step_idx)

    seg_start = ({seg.indices[0]: seg for seg in plan.segments}
                 if mesh is not None else {})
    idx = 0
    while idx < len(specs):
        seg = seg_start.get(idx)
        if seg is not None:
            items = _segment_items(graph, plan, seg, cache)
            sp = plan_segment(items, mesh)
            if not isinstance(sp, SegmentFallback):
                _seg_step(items, sp, seg.indices)
                idx = seg.indices[-1] + 1
                continue
            # the mesh cannot realize the costed placement: the members
            # run as local fused steps
            fallbacks.append(("segment", tuple(seg.indices), sp.reason))
        spec = specs[idx]
        if _fused(spec):
            _op, cplan = cache.get_or_build(graph, spec)
            roots = _spec_roots(spec)
            pl = getattr(spec, "placement", None)
            sp = None
            if mesh is not None and pl is not None \
                    and pl.arm == "distributed":
                items = [SegmentItem(cplan, pl, roots, True)]
                sp = plan_segment(items, mesh)
                if isinstance(sp, SegmentFallback):
                    fallbacks.append(("operator", (idx,), sp.reason))
                    sp = None
            if sp is not None:
                _seg_step(items, sp, (idx,))
            else:
                _fused_step(idx, cplan, roots)
        else:
            node = graph.by_id[spec.root]
            spec_step[idx] = len(steps)
            steps.append(("basic", node))
            key_parts.append((
                "basic", node.op,
                tuple(sorted(node.attrs.items())), node.shape,
                tuple(canon[i.nid] if i.op != "lit"
                      else ("lit", float(i.attrs["value"]))
                      for i in node.inputs)))
            canon[spec.root] = ("s", spec_step[idx], 0, 0)
        idx += 1

    # dead intermediates, re-indexed from spec positions to steps
    free: list[list[int]] = [[] for _ in steps]
    for sidx, dead in _last_uses(plan).items():
        free[spec_step[sidx]].extend(d for d in dead if d not in output_ids)
    key = (tuple(key_parts), tuple(canon[o] for o in output_ids), pallas,
           tuple(getattr(plan, "rewrite", ()) or ()))
    return StepTable(tuple(steps), key, tuple(fallbacks), tuple(rows),
                     tuple(tuple(f) for f in free))


@dataclass
class CompiledPlan:
    """Executable form of an ExecPlan.

    **Staged path (default).**  The entire plan — fused operators, basic
    ops, literals, multi-aggregate unpacking, and distributed segments —
    is traced into *one* function and jitted once, so a plan call is a
    single XLA dispatch: operator boundaries are XLA values instead of
    Python round-trips, literals are trace constants, and dead
    intermediates are released at their last use (``_last_uses``) so XLA
    reuses their buffers — the paper's 'fewer materialized intermediates'
    lifted from the operator level to the plan level.  Inputs are never
    donated: re-calling with the same arrays is always valid.  Staged
    functions are shared across structurally-equal plans via the
    :class:`WholePlanCache`.

    **Per-operator path** (``staged=False`` only — an explicit debug
    request, never an automatic downgrade): run specs in dependency
    order, one dispatch per fused operator, freeing intermediates when
    their last consumer has run — the pre-staging interpreter.

    When the plan was selected under a mesh layout, fused operators whose
    placement is ``"distributed"`` execute their generated body inside
    ``shard_map`` over the layout's real mesh with the template's
    collective epilogue (:mod:`repro.kernels.distributed`); the staged
    path lowers each plan :class:`~repro.core.select.Segment` — a run of
    adjacent distributed operators — into a *single* ``shard_map`` region
    whose row-sharded intermediates flow shard-to-shard and whose body
    runs the Pallas template kernels over shard-local shapes when
    ``pallas`` is enabled.  Row-sharded BCSR operands are block-row-
    partitioned outside ``jit`` (:class:`~repro.kernels.blocksparse.
    ShardedBCSR`) so sparse mains execute inside the region too.  Every
    downgrade to local execution is recorded in :attr:`fallbacks` with
    its reason — surfaced via ``explain()['execution']['fallbacks']``
    and raised under ``verify="strict"`` when a costed placement on a
    *real* mesh is abandoned.  One plan, hybrid execution."""
    plan: ExecPlan
    pallas: str = "never"
    cache: PlanCache = field(default_factory=lambda: PLAN_CACHE)
    #: FusionLayout the plan was selected under (None: local-only)
    layout: Optional[object] = None
    #: whole-plan staged execution (False: per-operator debug dispatch)
    staged: bool = True
    #: raise when a costed distributed placement is abandoned at
    #: execution time on a real mesh (FusionContext(verify="strict"))
    strict: bool = False
    #: name of the staged program's XLA module (``repro.obs.program_name``
    #: of the region; None: ``plan_fn``)
    name: Optional[str] = None
    #: per-(spec index, mesh) compiled shard_map callables for the per-op
    #: path (False: not realizable) — keyed by the mesh so a plan
    #: re-targeted at a different real mesh can't reuse a stale executable
    _dist_fns: dict = field(default_factory=dict, repr=False)
    #: literal (1, 1) arrays, built once per plan (per-op path)
    _lit_cache: Optional[dict] = field(default=None, repr=False)
    #: staged lowerings by the input positions whose Row kernels run
    #: lane-major (frozenset(): the row-major one) -> (jitted, raw, key)
    _staged: dict = field(default_factory=dict, repr=False)
    #: spec index -> the orientation the last call chose for that Row
    #: kernel (``explain()["execution"]["row_orientation"]``)
    _chosen: dict = field(default_factory=dict, repr=False)
    #: recorded execution downgrades, deduped by (site, reason, specs)
    _fallbacks: dict = field(default_factory=dict, repr=False)
    #: BCSR partition memo: (nid, nparts, id(data)) -> (data, ShardedBCSR)
    _part_cache: dict = field(default_factory=dict, repr=False)

    # -- fallback observability --------------------------------------------

    def record_fallback(self, site: str, reason: str,
                        specs: Optional[tuple] = None,
                        hard: bool = False) -> None:
        """Log one execution downgrade (idempotent per site/reason/specs).
        ``hard`` marks a placement a *real* mesh could have executed —
        under ``strict`` that abandonment raises instead of downgrading."""
        key = (site, reason, specs)
        if key not in self._fallbacks:
            entry = {"site": site, "reason": reason}
            if specs is not None:
                entry["specs"] = list(specs)
            self._fallbacks[key] = entry
        if hard and self.strict:
            raise PlanInvariantError(
                f"verify=strict: costed distributed placement abandoned "
                f"at execution time ({site}): {reason}")

    @property
    def fallbacks(self) -> list:
        """Recorded execution downgrades (see ``explain()``)."""
        return list(self._fallbacks.values())

    # -- staged whole-plan path --------------------------------------------

    @functools.cached_property
    def table(self) -> StepTable:
        """The plan's steps under this layout and Pallas mode."""
        with obs.span(obs.CODEGEN):
            return plan_steps(self.plan, self.layout, self.pallas, self.cache)

    def staged_callable(self, args=None) -> tuple[Callable, Callable]:
        """(jitted whole-plan function, its un-jitted trace function),
        building them on first use.  Both take the graph's input arrays
        positionally (``graph.inputs()`` order) and return the tuple of
        graph outputs; the raw function is exposed so tests can inspect
        the plan's jaxpr (e.g. count ``shard_map`` regions).

        ``args`` (arrays or ``jax.ShapeDtypeStruct`` in that order)
        selects the Row kernels' orientation from the operands' device
        layouts (:meth:`lane_inputs`); without it every Row kernel is
        row-major."""
        lanes = frozenset() if args is None else self.lane_inputs(args)
        fn, raw, _key = self._staged_for(lanes)
        return fn, raw

    def _staged_for(self, lanes: frozenset) -> tuple[Callable, Callable,
                                                     tuple]:
        """(jitted, raw, whole-plan key) of the staged lowering whose Row
        kernels over the inputs at ``lanes`` run lane-major, built on
        first use."""
        hit = self._staged.get(lanes)
        if hit is None:
            hit = self._staged[lanes] = self._build_staged(lanes)
        return hit

    def lane_inputs(self, args) -> frozenset:
        """Positions of the inputs in ``args`` whose Row kernels run
        lane-major: the input is the main of a local Row kernel that has
        a lane-major form, and its device stores it column-major
        (:func:`device_layout` (1, 0)), as a TPU stores an (m, n) f32
        array whose n is not a multiple of 128.  Records each such
        kernel's orientation for :attr:`row_orientation`."""
        layouts: dict[int, Optional[tuple]] = {}
        for idx, pos, _reason in self.table.rows:
            if pos is None:
                continue
            if pos not in layouts:
                layouts[pos] = device_layout(args[pos])
            layout = layouts[pos]
            e = self._chosen[idx] = {"specs": [idx]}
            if layout == (1, 0):
                e["orientation"] = "lane_major"
                continue
            e["orientation"] = "row_major"
            e["reason"] = (f"the main's device layout is not readable "
                           f"(input {pos})" if layout is None else
                           f"the main is stored major_to_minor {layout} "
                           f"(input {pos})")
        return frozenset(p for p, lay in layouts.items() if lay == (1, 0))

    @property
    def row_orientation(self) -> list:
        """Each local Row kernel's orientation, as the last call chose
        it, with the reason where it is row-major."""
        return [dict(self._chosen.get(e["specs"][0], e))
                for e in self.table.orientations()]

    def _build_staged(self, lanes: frozenset = frozenset()
                      ) -> tuple[Callable, Callable, tuple]:
        table = self.table
        real_mesh = _is_real_mesh(_mesh_of(self.layout))
        for site, specs, reason in table.fallbacks:
            if site != "kernel":
                self.record_fallback(site, reason, specs=specs,
                                     hard=real_mesh)
        with obs.span(obs.CODEGEN) as sp:
            plan_fn = self._lower_staged(table, lanes)
        if self.name is not None:
            plan_fn.__name__ = plan_fn.__qualname__ = self.name
        key = table.key
        if lanes:
            key += (("rowt", tuple(sorted(lanes))),)
        # build-once under concurrency: racing threads compiling
        # structurally-equal plans share one jitted function (and with
        # it one XLA executable per shape signature)
        def _build():
            faults.fault_point("plan.jit_build")
            return jax.jit(plan_fn)

        jitted = WHOLE_PLAN_CACHE.get_or_create(
            key, _build, extra_build_s=sp.seconds)
        return jitted, plan_fn, key

    def _lower_staged(self, table: StepTable,
                      lanes: frozenset) -> Callable:
        """The un-jitted function that runs ``table``'s steps; Row
        kernels whose main is the input at a position in ``lanes`` run
        lane-major."""
        from repro.kernels.distributed import (
            SegmentFallback, lower_segment, run_segment_local)

        graph = self.plan.graph
        in_nids = tuple(n.nid for n in graph.inputs())
        lits = tuple((n.nid, float(n.attrs["value"]))
                     for n in graph.nodes if n.op == "lit")
        output_ids = tuple(o.nid for o in graph.outputs)
        mesh, pallas = _mesh_of(self.layout), self.pallas
        steps, free = table.steps, table.free

        def _mat(v):
            # a value partitioned for a segment, consumed whole elsewhere
            return v.unshard() if isinstance(v, ShardedBCSR) else v

        def _put(env, out, roots):
            if len(roots) > 1:
                for k, r in enumerate(roots):
                    env[r] = out[k].reshape(1, 1)
            else:
                env[roots[0]] = out

        def plan_fn(*arrays):
            env: dict[int, object] = dict(zip(in_nids, arrays))
            for nid, v in lits:         # trace-time constants
                env[nid] = jnp.full((1, 1), v, jnp.float32)
            for step, dead_after in zip(steps, free):
                kind = step[0]
                if kind == "seg":
                    _, sp, out_roots = step
                    vals = [env[nid] for nid in sp.ext]
                    # trace-time lowering: in_specs chosen from the
                    # actual value formats (jit retraces per pytree
                    # structure, so each format gets its own lowering)
                    lowered = lower_segment(sp, mesh, vals, pallas=pallas)
                    if isinstance(lowered, SegmentFallback):
                        # recorded by __call__'s preflight; numerically
                        # identical local execution (collectives exact)
                        outs = run_segment_local(sp, vals, pallas=pallas)
                    else:
                        outs = lowered(*vals)
                    for out, roots in zip(outs, out_roots):
                        _put(env, out, roots)
                elif kind == "fused":
                    _, cplan, bind_nids, roots, main_pos = step
                    _put(env, kops.execute(
                        cplan, {nid: _mat(env[nid]) for nid in bind_nids},
                        pallas=pallas, lanes=main_pos in lanes), roots)
                else:
                    node = step[1]
                    env[node.nid] = _eval_basic(graph, node, env)
                for dead in dead_after:
                    env.pop(dead, None)      # release: XLA reuses buffers
            return tuple(_mat(env[o]) for o in output_ids)

        return plan_fn

    def batched_callable(self) -> Callable:
        """Jitted ``vmap`` of the staged whole-plan function over a new
        leading request axis — the executable the fused-plan server
        (:mod:`repro.serve.fusion`) dispatches one *batch* of
        same-structure requests through.  Takes each graph input stacked
        to ``(B, *shape)`` (``graph.inputs()`` order) and returns every
        output stacked the same way; batch elements are computed
        independently (vmap semantics), so the result equals B separate
        calls.  Mesh-free plans only: ``vmap`` over a ``shard_map``
        segment is not supported.  Shared across structurally-equal
        plans via the whole-plan cache (key ``("vmap", staged key)``)."""
        if _mesh_of(self.layout) is not None:
            raise PlanInvariantError(
                "batched_callable: batched (vmapped) execution requires "
                "a mesh-free plan; this plan was compiled under a layout")
        _fn, raw, key = self._staged_for(frozenset())
        key = ("vmap", key)

        def _build():
            faults.fault_point("plan.jit_build")
            return jax.jit(jax.vmap(raw))

        return WHOLE_PLAN_CACHE.get_or_create(key, _build)

    # -- per-operator fallback path ----------------------------------------

    def _dist_call(self, idx: int, spec, cplan, env: dict[int, object]):
        """Run one distributed-placed operator, or None to fall back —
        recording the downgrade reason (and raising under strict when a
        real mesh abandons its costed placement)."""
        pl = getattr(spec, "placement", None)
        if pl is None or pl.arm != "distributed" or self.layout is None:
            return None
        mesh = _mesh_of(self.layout)
        from repro.kernels.distributed import build_dist_fn
        vals = [env[b.nid] for b in cplan.binds]
        built, fb = build_dist_fn(cplan, mesh, pl, pallas=self.pallas,
                                  values=vals)
        if built is None:
            self.record_fallback("operator", fb.reason, specs=(idx,),
                                 hard=_is_real_mesh(mesh))
            return None
        fn, prepared = built
        return fn(*prepared)

    def _literals(self, graph: Graph) -> dict[int, object]:
        if self._lit_cache is None:
            self._lit_cache = {
                node.nid: jnp.full((1, 1), float(node.attrs["value"]),
                                   jnp.float32)
                for node in graph.nodes if node.op == "lit"}
        return self._lit_cache

    def _call_per_op(self, bindings: dict[str, object]):
        graph = self.plan.graph
        env: dict[int, object] = {}
        for node in graph.inputs():
            env[node.nid] = bindings[node.name]
        env.update(self._literals(graph))

        last_use = _last_uses(self.plan)
        for idx, spec in enumerate(self.plan.specs):
            if _fused(spec):
                op, my_cplan = self.cache.get_or_build(graph, spec)
                out = self._dist_call(idx, spec, my_cplan, env)
                if out is None:
                    # positional re-binding: cached operator's nids ≠ ours
                    op_env = {ob.nid: env[mb.nid] for ob, mb in
                              zip(op.cplan.binds, my_cplan.binds)}
                    out = op(op_env, pallas=self.pallas)
                if isinstance(spec, MultiAggSpec):
                    for k, r in enumerate(spec.roots):
                        env[r] = out[k].reshape(1, 1)
                else:
                    env[spec.root] = out
            else:
                env[spec.root] = _eval_basic(graph, graph.by_id[spec.root],
                                             env)
            for dead in last_use.get(idx, ()):    # free intermediates
                if dead not in graph.output_ids:
                    env.pop(dead, None)
        outs = [env[o.nid] for o in graph.outputs]
        return outs[0] if len(outs) == 1 else tuple(outs)

    # -- sharded sparse input preparation -----------------------------------

    def _partition_memo(self, nid: int, v: BCSR, nparts: int):
        """Memoized block-row partition of a concrete BCSR input (O(nnz)
        host work — cached by data-array identity so steady-state calls
        with the same matrix pay it once)."""
        from repro.kernels.blocksparse import partition_block_rows
        key = (nid, nparts, id(v.data))
        hit = self._part_cache.get(key)
        if hit is not None and hit[0] is v.data:
            return hit[1]
        part = partition_block_rows(v, nparts)
        if part is not None:
            if len(self._part_cache) > 16:
                self._part_cache.clear()
            self._part_cache[key] = (v.data, part)
        return part

    def _prepare_inputs(self, vals: dict[int, object]) -> None:
        """Preflight for the staged call: block-row-partition graph-input
        BCSRs that a ``shard_map`` segment consumes row-sharded (must run
        outside ``jit`` — re-bucketing needs concrete indices), recording
        every operand that forces the segment to run locally instead."""
        for step in self.table.steps:
            if step[0] != "seg":
                continue
            sp = step[1]
            sparse_noagg = {it.cplan.main.nid for it in sp.items
                            if it.export and it.cplan.variant == NO_AGG
                            and it.cplan.main.exploit}
            for nid in sp.ext:
                if not sp.ext_shard[nid] or nid not in vals:
                    continue
                v = vals[nid]
                if isinstance(v, BCSR):
                    if nid in sparse_noagg:
                        self.record_fallback(
                            "segment",
                            f"sparse no_agg output of operand %{nid} "
                            f"cannot cross the shard_map boundary",
                            hard=True)
                        continue
                    part = self._partition_memo(nid, v, sp.n)
                    if part is None:
                        self.record_fallback(
                            "segment",
                            f"sparse operand %{nid}: "
                            f"{v.shape[0] // v.bs} block rows not "
                            f"partitionable across {sp.n} shards",
                            hard=True)
                    else:
                        vals[nid] = part
                elif isinstance(v, DictCompressed):
                    self.record_fallback(
                        "segment",
                        f"row-sharded operand %{nid} is CLA-compressed: "
                        f"no distributed decompression path", hard=True)

    # -- entry point ---------------------------------------------------------

    def __call__(self, bindings: dict[str, object]):
        graph = self.plan.graph
        for node in graph.inputs():
            if node.name not in bindings:
                raise KeyError(f"missing binding for input '{node.name}'")
        if not self.staged:
            return self._call_per_op(bindings)
        vals = {n.nid: bindings[n.name] for n in graph.inputs()}
        self._prepare_inputs(vals)
        args = [vals[n.nid] for n in graph.inputs()]
        fn, _raw, key = self._staged_for(self.lane_inputs(args))
        if WHOLE_PLAN_CACHE.first_call(key, args):
            with obs.span(obs.STAGE):
                outs = fn(*args)
        else:
            outs = fn(*args)
        return outs[0] if len(outs) == 1 else tuple(outs)


def device_layout(v) -> Optional[tuple]:
    """The major-to-minor order in which the device stores the 2-D
    array ``v``, or None where it cannot be read: a tracer, a sparse or
    compressed operand, an array on more than one device.  A host array
    and a ``jax.ShapeDtypeStruct`` (an AOT compile) without a layout of
    its own read the default layout for their shape and dtype of the
    device they go to (the default device for a host array)."""
    if isinstance(v, jax.core.Tracer) or getattr(v, "ndim", 0) != 2:
        return None
    try:
        if isinstance(v, jax.Array):
            if len(v.sharding.device_set) != 1:
                return None
            return v.format.layout.major_to_minor
        if isinstance(v, jax.ShapeDtypeStruct):
            if v.format.layout is not None:
                return v.format.layout.major_to_minor
            (dev,) = v.sharding.device_set
        elif isinstance(v, np.ndarray):
            dev = jax.config.jax_default_device or jax.devices()[0]
        else:
            return None
        return _default_layout(dev, tuple(v.shape), np.dtype(v.dtype))
    except Exception:                 # noqa: BLE001 — unreadable layout
        return None


@functools.lru_cache(maxsize=256)
def _default_layout(dev, shape: tuple, dtype) -> tuple:
    return Layout.from_pjrt_layout(dev.client.get_default_layout(
        dtype, shape, dev)).major_to_minor


def _last_uses(plan: ExecPlan) -> dict[int, list[int]]:
    last: dict[int, int] = {}
    for idx, spec in enumerate(plan.specs):
        for i in spec.inputs:
            last[i] = idx
    out: dict[int, list[int]] = {}
    for nid, idx in last.items():
        out.setdefault(idx, []).append(nid)
    return out


def staged_plan_key(plan: ExecPlan, pallas: str = "never",
                    cache: Optional[PlanCache] = None) -> tuple:
    """The structural whole-plan cache key of the mesh-free staged
    lowering (:func:`plan_steps`), computed without tracing or jitting
    anything: the server's bucketing identity and the verifier's
    key-completeness check (:func:`repro.core.verify.verify_exec`,
    EXE004)."""
    return plan_steps(plan, None, pallas, cache).key


def plan_fallbacks(plan: ExecPlan, layout=None, pallas: str = "never",
                   staged: bool = True,
                   cache: Optional[PlanCache] = None) -> list:
    """Statically derivable execution downgrades for this plan — the
    compile-time portion of ``explain()['execution']['fallbacks']``
    (:meth:`StepTable.fallback_report`).  Value-format downgrades (a
    sparse operand whose block rows don't partition) depend on the bound
    arrays and are recorded at call time on
    :attr:`CompiledPlan.fallbacks`; ``Compiled.explain()`` merges both."""
    return plan_steps(plan, layout, pallas, cache).fallback_report(staged)


def row_orientations(plan: ExecPlan, pallas: str = "never", layout=None,
                     cache: Optional[PlanCache] = None) -> list:
    """The static part of ``explain()["execution"]["row_orientation"]``
    (:meth:`StepTable.orientations`)."""
    return plan_steps(plan, layout, pallas, cache).orientations()


def compile_plan(plan: ExecPlan, pallas: str = "never",
                 layout=None, staged: bool = True,
                 strict: bool = False,
                 name: Optional[str] = None) -> CompiledPlan:
    """Bind an ExecPlan to its executable form.

    ``staged=True`` (default) compiles the whole plan into a single
    jitted computation (one dispatch per call, whole-plan cached) for
    every operand format and Pallas mode — BCSR mains and
    ``pallas="interpret"`` included; ``staged=False`` selects the
    per-operator interpreter dispatch, an explicit debug path.  Every
    execution downgrade is recorded on :attr:`CompiledPlan.fallbacks`;
    ``strict=True`` (``FusionContext(verify="strict")``) raises when a
    costed distributed placement on a real mesh is abandoned at
    execution time.  ``name`` names the staged program's XLA module.
    The per-template dispatch rules are tabulated in
    ``docs/architecture.md`` (kernel-dispatch decision table)."""
    return CompiledPlan(plan, pallas=pallas, layout=layout, staged=staged,
                        strict=strict, name=name)
