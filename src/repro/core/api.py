"""Public fusion API: the staged ``trace → plan → compile`` pipeline.

The paper's three optimizer phases (candidate exploration, cost-based
selection, code generation) are exposed as explicit, inspectable stages —
the JAX-AOT-style analogue of SystemML separating compilation from
execution:

    hinge = fused(lambda X, w, y: ir.relu(1 - y * (X @ w)))

    traced   = hinge.trace(Xarr, warr, yarr)      # IR graph, static shapes
    planned  = traced.plan(mode="gen")            # explore → select
    print(planned.explain())                      # per-candidate cost report
    op       = planned.compile(pallas="never")    # generated fused operators
    out      = op(Xarr, warr, yarr)

``@fused`` call syntax stays as sugar over the staged path: the wrapper
traces/plans/compiles on first call per (shape, format, context) signature
and memoizes the Compiled stage.

Compiled fused operators are first-class JAX citizens:

* **autodiff** — each dense call runs through a ``jax.custom_vjp`` whose
  backward pass is *itself* planned through explore → select
  (:mod:`repro.core.grad`), so ``jax.grad`` of a ``@fused`` region executes
  generated fused operators in both directions.  Only the gradients of
  the inputs JAX differentiates are planned and run.
* **layouts** — ``plan(layout=mesh_or_FusionLayout)`` threads the PR-2
  distributed layout rules onto operator inputs/outputs: reads of
  model-sharded side inputs are costed at ICI bandwidth during selection,
  and dense operands are sharding-constrained at execution
  (:mod:`repro.core.layout`), so local and distributed execution share one
  entry point.

Operands may be 2-D matrices, 1-D vectors, or 0-D scalars; non-2-D inputs
are canonicalized to column / 1×1 matrices for planning.  **Round-trip
rule:** when a call passes any 1-D/0-D operand, outputs of shape ``(n, 1)``
are returned as 1-D ``(n,)`` and ``(1, 1)`` outputs as 0-D scalars; calls
made entirely with 2-D operands always return 2-D results.

Contexts are immutable and explicitly scoped (:class:`FusionContext`);
``fusion_mode(...)`` remains as derive-and-scope sugar.
"""

from __future__ import annotations

import inspect
from dataclasses import dataclass, field, replace
from typing import Callable, Optional

import jax.numpy as jnp

from repro import obs
from repro.kernels.blocksparse import BCSR, DictCompressed
from . import ir
from .codegen import CompiledPlan, compile_plan, plan_steps
from .context import FusionContext, current_context
from .cost import CostParams
from .grad import vjp_graph
from .layout import FusionLayout, ensure_layout, layout_cost_params
from .select import ExecPlan, MODES, MultiAggSpec, plan as plan_graph
from .verify import VerifyReport, verify_exec, verify_plan


class FusionInputError(TypeError):
    """An operand cannot be lifted into the 2-D LinOp IR."""


# --------------------------------------------------------------------------
# operand canonicalization (1-D vectors / 0-D scalars → column / 1×1)
# --------------------------------------------------------------------------

def _canon_shape(name: str, v) -> tuple[tuple[int, int], int]:
    """(canonical 2-D shape, original ndim) of one operand.

    This is where the 1-D/0-D canonicalization is *enforced*: the LinOp
    IR is strictly 2-D, so a 1-D vector of length n plans as an (n, 1)
    column matrix and a 0-D / python scalar as (1, 1).  The original
    ndim is kept so :func:`_uncanon_output` can round-trip results
    (column → 1-D, 1×1 → 0-D) for calls that passed any non-2-D operand;
    ranks above 2 raise :class:`FusionInputError`."""
    if isinstance(v, (BCSR, DictCompressed)):
        return tuple(v.shape), 2
    if isinstance(v, (int, float)):
        return (1, 1), 0
    shape = tuple(getattr(v, "shape", None) or ())
    if not hasattr(v, "shape"):
        raise FusionInputError(
            f"argument '{name}': expected an array, matrix, or scalar, "
            f"got {type(v).__name__}")
    if len(shape) == 2:
        return shape, 2
    if len(shape) == 1:
        return (shape[0], 1), 1           # column-vector convention
    if len(shape) == 0:
        return (1, 1), 0
    raise FusionInputError(
        f"argument '{name}': expected 0-D, 1-D or 2-D, got shape {shape}")


def _canon_value(name: str, v):
    shape, nd = _canon_shape(name, v)
    if nd == 2:
        return v
    if isinstance(v, (int, float)):
        return jnp.full((1, 1), float(v), jnp.float32)
    return jnp.reshape(v, shape)


def _uncanon_output(out):
    """The output half of the canonicalization round-trip, applied by
    :meth:`Compiled.__call__` iff the call passed any 1-D/0-D operand
    ("vector-world"): (n, 1) columns → 1-D ``(n,)``, (1, 1) → 0-D.
    All-2-D calls skip this and always get 2-D results back."""
    shape = getattr(out, "shape", ())
    if shape == (1, 1):
        return jnp.reshape(out, ())
    if len(shape) == 2 and shape[1] == 1:
        return jnp.reshape(out, (shape[0],))
    return out


def _as_expr_inputs(args: dict[str, object],
                    sparsity: dict[str, float]) -> dict[str, ir.Expr]:
    out = {}
    for name, v in args.items():
        shape, _ = _canon_shape(name, v)
        if isinstance(v, BCSR):
            sp = sparsity.get(name, v.block_sparsity)
        else:
            sp = sparsity.get(name, 1.0)
        out[name] = ir.matrix(name, shape, sparsity=sp)
    return out


def _signature(args: dict[str, object], ctx: FusionContext):
    sig: list = [ctx.key()]
    for name, v in args.items():
        if isinstance(v, BCSR):
            sig.append((name, "bcsr", v.shape, v.bs,
                        round(v.block_sparsity, 4)))
        elif isinstance(v, DictCompressed):
            sig.append((name, "dict", v.shape))
        else:
            shape, nd = _canon_shape(name, v)
            sig.append((name, "dense", shape, nd))
    return tuple(sig)


# --------------------------------------------------------------------------
# stage 1: Traced — the IR graph of the expression at static shapes
# --------------------------------------------------------------------------

@dataclass
class Traced:
    """Abstract trace of an expression function: the HOP DAG plus operand
    metadata.  Planning-only — carries no array data."""

    name: str
    graph: ir.Graph
    in_names: list[str]                    # fn-signature order
    in_meta: dict[str, dict]               # name → {shape, format, sparsity}

    def plan(self, mode: Optional[str] = None,
             params: Optional[CostParams] = None,
             layout=None,
             context: Optional[FusionContext] = None) -> "Planned":
        """Stage 2: run explore → select, returning a :class:`Planned`.

        Arguments (each optional, overriding the scoped
        :class:`FusionContext`):

        mode
            Selection arm: ``"gen"`` (cost-based MPSkipEnum — the paper's
            contribution), ``"fa"`` (fuse-all), ``"fnr"``
            (fuse-no-redundancy), or ``"none"`` (no fusion).
        params
            :class:`CostParams` cost-model constants (roofline
            bandwidths, byte widths, the fused-input constraint).
        layout
            A :class:`FusionLayout`, or any mesh exposing
            ``.shape``/``.axis_names`` — including the abstract
            ``repro.dist.LogicalMesh``, so no devices are required —
            which is auto-fitted to this trace's operand shapes via the
            PR-1/2 sharding rules.  With a layout, selection prices
            every fused operator on both the local and the distributed
            arm (``shard_map`` body + collective epilogue) and the
            induced plan is *hybrid*: per-operator placement is reported
            by :meth:`Planned.explain`.
        context
            Explicit base context (defaults to :func:`current_context`).
        """
        ctx = context if context is not None else current_context()
        if mode is not None:
            ctx = ctx.with_(mode=mode)
        if params is not None:
            ctx = ctx.with_(params=params)
        if layout is not None:
            ctx = ctx.with_(layout=layout)
        with obs.span(obs.PLAN):
            if ctx.layout is not None and not isinstance(ctx.layout,
                                                         FusionLayout):
                # bare mesh (incl. via the scoped context): fit the
                # sharding rules to this trace's operand and output shapes
                shapes = {name: m["shape"]
                          for name, m in self.in_meta.items()}
                ctx = ctx.with_(layout=ensure_layout(
                    ctx.layout, self.graph, extra_shapes=shapes))
            eff = layout_cost_params(ctx.layout, self.graph, ctx.params)
            eplan = plan_graph(self.graph, ctx.mode, eff)
            rw_report = None
            if ctx.rewrite:
                with obs.span(obs.PLAN_REWRITE):
                    eplan, rw_report = _rewrite_sweep(self.graph, ctx,
                                                      eplan)
            planned = _verified_planned(self, ctx, eplan)
        planned._rewrite = rw_report
        return planned


# --------------------------------------------------------------------------
# stage 2: Planned — a selected ExecPlan with costs and an explain() report
# --------------------------------------------------------------------------

def _rewrite_sweep(graph: ir.Graph, ctx: FusionContext,
                   base: ExecPlan) -> tuple[ExecPlan, dict]:
    """The SPORES-style variant sweep between trace and plan: generate
    algebraically-equal DAG variants (:mod:`repro.core.rewrite`), gate
    each through the rewrite verifier (RW001–RW004 — always at least
    ``"cheap"``, even under ``verify="off"``: rejecting an illegal
    variant is a correctness property, not a diagnostic), plan the clean
    ones through the same explore → select pipeline, and return the
    global cost argmin plus the ``explain()["rewrite"]`` report.

    Deterministic: variants come out of the bounded BFS in a fixed
    order, plans tie-break toward the earlier variant (and the original
    DAG before any variant), and rule labels use topological indices —
    so re-tracing the same expression reproduces the report verbatim."""
    from .rewrite import rewrite_variants
    from .verify import verify_variant

    level = "strict" if ctx.verify == "strict" else "cheap"
    variants = rewrite_variants(graph)
    entries = [{"rules": [], "cost": base.cost, "selected": False}]
    rejected: list[dict] = []
    best, best_idx, best_rules = base, 0, ()
    for v in variants:
        vrep = verify_variant(graph, v.graph, level=level)
        if not vrep.ok:
            rejected.append({"rules": list(v.rules),
                             "errors": sorted({d.code
                                               for d in vrep.errors})})
            continue
        eff_v = layout_cost_params(ctx.layout, v.graph, ctx.params)
        ep = plan_graph(v.graph, ctx.mode, eff_v)
        entries.append({"rules": list(v.rules), "cost": ep.cost,
                        "selected": False})
        if ep.cost < best.cost:
            best, best_idx, best_rules = ep, len(entries) - 1, v.rules
    entries[best_idx]["selected"] = True
    best.rewrite = tuple(best_rules)
    report = {
        "enabled": True,
        "n_variants": len(variants),
        "n_planned": len(entries) - 1,
        "n_rejected": len(rejected),
        "rejected": rejected,
        "variants": entries,
        "winner": {
            "rules": list(best_rules),
            "cost": best.cost,
            "baseline_cost": base.cost,
            "improvement": base.cost - best.cost,
        },
    }
    return best, report


def _verified_planned(traced: Traced, ctx: FusionContext,
                      eplan: ExecPlan) -> "Planned":
    """The plan() stage boundary: every ExecPlan entering stage 2 passes
    the plan verifier at the context's level (``"cheap"`` by default,
    ``"strict"`` for the full pass, ``"off"`` to skip).  Error-severity
    diagnostics raise :class:`~repro.core.verify.VerificationError`
    here — before any code generation can execute the broken plan."""
    planned = Planned(traced, ctx, eplan)
    if ctx.verify != "off":
        with obs.span(obs.PLAN_VERIFY):
            report = verify_plan(eplan, level=ctx.verify,
                                 pallas=ctx.pallas, layout=ctx.layout)
            report.raise_if_errors()
        planned._verify = report
    return planned


def _spec_signature(graph: ir.Graph, spec) -> dict:
    def label(nid: int) -> str:
        n = graph.by_id[nid]
        return n.name if n.name else n.op

    if isinstance(spec, MultiAggSpec):
        return {"template": "MAGG(multi)",
                "root": [graph.by_id[r].op for r in spec.roots],
                "inputs": sorted(label(i) for i in spec.inputs),
                "driver": None,
                "n_covered": sum(len(p.cover) for p in spec.parts)}
    return {"template": spec.ttype.name if spec.ttype is not None else "basic",
            "root": graph.by_id[spec.root].op,
            "inputs": sorted(label(i) for i in spec.inputs),
            "driver": label(spec.driver) if spec.driver is not None else None,
            "n_covered": len(spec.cover)}


@dataclass
class Planned:
    """One selected execution plan for a Traced expression."""

    traced: Traced
    context: FusionContext
    eplan: ExecPlan
    #: planned backward per frozenset of differentiated input names
    _bwd: dict = field(default_factory=dict, repr=False)
    #: VerifyReport from the plan() stage boundary (None: verify="off")
    _verify: Optional[VerifyReport] = field(default=None, repr=False)
    #: rewrite-sweep report from Traced.plan() (None: ctx.rewrite=False or
    #: a path that never swept, e.g. the planned backward)
    _rewrite: Optional[dict] = field(default=None, repr=False)

    @property
    def cost(self) -> float:
        return self.eplan.cost

    def fused_signatures(self) -> list[dict]:
        """Structural signature of every selected fused operator.  Under a
        mesh layout each signature also carries the local/distributed
        decision: ``placement``, the collective ``epilogue``, and the
        modeled per-device ``collective_bytes`` (ring all-reduce of the
        epilogue plus side-input all-gathers)."""
        out = []
        for s in self.eplan.fused_specs():
            sig = _spec_signature(self.eplan.graph, s)
            pl = getattr(s, "placement", None)
            if pl is not None:
                sig["placement"] = pl.arm
                sig["epilogue"] = pl.epilogue
                sig["collective_bytes"] = int(round(pl.collective_bytes))
            out.append(sig)
        return out

    def candidates(self) -> list[dict]:
        """Cost every selection arm for this plan's graph (the
        per-candidate report, analogous to the layout planner's candidate
        sweep).  Uses ``eplan.graph`` — when the rewrite sweep won, the
        arms are costed on the *winning variant*, so the table compares
        like with like."""
        eff = layout_cost_params(self.context.layout, self.eplan.graph,
                                 self.context.params)
        out = []
        for m in MODES:
            p = self.eplan if m == self.context.mode \
                else plan_graph(self.eplan.graph, m, eff)
            out.append({"mode": m, "cost": p.cost,
                        "n_fused": len(p.fused_specs()),
                        "n_operators": len(p.specs),
                        "selected": m == self.context.mode})
        return out

    def backward(self, wrt=None) -> "Planned":
        """Plan the gradient DAG through the same explore → select pipeline
        (fused backward operators).  ``wrt`` names the inputs whose
        gradients the DAG computes (None: every input); gradients no
        caller reads are not planned, so their passes and outputs never
        run.  ``grad_names`` lists the planned gradients in input order.
        Raises NonDifferentiableError when the forward graph has an op
        with no VJP rule."""
        fwd_inputs = [n.name for n in self.eplan.graph.inputs()]
        if wrt is not None:
            want = set(wrt)
            wrt = [n for n in fwd_inputs if n in want]
        key = frozenset(fwd_inputs if wrt is None else wrt)
        if key in self._bwd:
            return self._bwd[key]
        with obs.span(obs.PLAN):
            ct_names, grads = vjp_graph(self.eplan.graph)
            grad_names = fwd_inputs if wrt is None else wrt
            bgraph = ir.Graph.build([grads[n] for n in grad_names])
            in_meta = dict(self.traced.in_meta)
            for name, o in zip(ct_names, self.eplan.graph.outputs):
                in_meta[name] = {"shape": o.shape, "format": "dense",
                                 "sparsity": 1.0}
            btr = Traced(self.traced.name + ":vjp", bgraph,
                         list(self.traced.in_names) + ct_names, in_meta)
            bwd = _verified_planned(
                btr, self.context,
                plan_graph(bgraph, self.context.mode,
                           layout_cost_params(self.context.layout, bgraph,
                                              self.context.params)))
            bwd.grad_names = grad_names   # type: ignore[attr-defined]
        self._bwd[key] = bwd
        return bwd

    def explain(self, include_backward: bool = False) -> dict:
        """Structured plan report (same shape as the layout planner's
        ``experiments/layouts`` JSON: winner + candidates + stats).

        Keys: ``expression``, ``mode``, ``inputs`` (shape/format/
        sparsity per operand), ``winner`` (cost, operator count, and one
        signature per fused operator — see :meth:`fused_signatures`),
        ``candidates`` (every selection arm costed on this trace),
        ``rewrite`` (the trace→plan algebraic-variant sweep: rules
        applied, per-variant cost, rejected variants with their RW
        codes, and the winning rule chain — ``{"enabled": False}`` when
        the context disabled it), ``stats`` (exploration/enumeration
        counters), ``execution``
        (staged whole-plan compilation: the per-call dispatch count, the
        dead intermediates the staged trace frees for buffer reuse, and
        the guarantee that inputs are never donated), and ``layout``
        (mesh + PartitionSpecs, or None).  Under a mesh layout a
        ``distributed`` summary is added: row-shard axes and degree, the
        local/distributed operator split, total modeled collective
        volume, and the plan ``segments`` — runs of adjacent distributed
        operators that execute inside a single ``shard_map`` region,
        each with the intra-segment boundary volume the fused region
        removes (``removed_collective_bytes``).  ``verify`` carries the
        plan verifier's report (:mod:`repro.core.verify`): the level it
        ran at, error/warning counts, and every diagnostic.
        ``include_backward=True`` appends the planned gradient DAG's
        report (see :meth:`backward`)."""
        ex, en = self.eplan.explore_stats, self.eplan.enum_stats
        steps = plan_steps(self.eplan, self.context.layout,
                           self.context.pallas)
        report = {
            "expression": self.traced.name,
            "mode": self.context.mode,
            "inputs": {n: {"shape": list(m["shape"]),
                           "format": m["format"],
                           "sparsity": round(float(m["sparsity"]), 4)}
                       for n, m in self.traced.in_meta.items()},
            "winner": {
                "cost": self.eplan.cost,
                "n_operators": len(self.eplan.specs),
                "operators": self.fused_signatures(),
            },
            "candidates": self.candidates(),
            # the trace→plan rewrite sweep (rules applied, per-variant
            # cost, winner); {"enabled": False} when the context disabled
            # it or this Planned came from a path that never sweeps
            "rewrite": (self._rewrite if self._rewrite is not None
                        else {"enabled": False}),
            "stats": {
                "explored_operators": ex.operators if ex else 0,
                "memo_entries": ex.entries_kept if ex else 0,
                "partitions": en.partitions if en else 0,
                "enum_points": en.points_total if en else 0,
                "plans_costed": en.plans_costed if en else 0,
            },
            "execution": {
                "staged": self.context.staged,
                "dispatches_per_call": 1 if self.context.staged
                else len(self.eplan.specs),
                "donated_inputs": [],       # inputs are never donated
                "freed_intermediates": sum(map(len, steps.free)),
                # every statically-known execution downgrade, with its
                # reason; Compiled.explain() merges the runtime-recorded
                # entries (value-format downgrades seen at call time)
                "fallbacks": steps.fallback_report(self.context.staged),
            },
            "layout": None,
        }
        if self.context.pallas != "never" and self.context.staged:
            # each Row kernel's orientation (row- or lane-major), decided
            # per call by its main's device layout; Compiled.explain()
            # puts in what the last call chose
            report["execution"]["row_orientation"] = steps.orientations()
        if self._verify is None and self.context.verify != "off":
            self._verify = verify_plan(self.eplan,
                                       level=self.context.verify,
                                       pallas=self.context.pallas,
                                       layout=self.context.layout)
        report["verify"] = (self._verify.summary()
                           if self._verify is not None else None)
        if self.context.layout is not None:
            lay = self.context.layout
            report["layout"] = {
                "mesh": {a: int(lay.mesh.shape[a])
                         for a in lay.mesh.axis_names},
                "specs": {n: [list(e) if isinstance(e, tuple) else e
                              for e in tuple(s)]
                          for n, s in sorted(lay.specs.items())},
            }
            ops = report["winner"]["operators"]
            n_dist = sum(1 for o in ops
                         if o.get("placement") == "distributed")
            segments = [{
                "specs": list(seg.indices),
                "n_operators": len(seg.indices),
                "row_axes": list(seg.axes),
                "devices": seg.n,
                "n_sharded_edges": len(seg.sharded_edges),
                "removed_collective_bytes":
                    int(round(seg.removed_gather_bytes)),
            } for seg in self.eplan.segments]
            report["distributed"] = {
                "row_axes": list(lay.row_axes()),
                "devices": lay.row_devices(),
                "n_fused_local": len(ops) - n_dist,
                "n_fused_distributed": n_dist,
                "collective_bytes": sum(o.get("collective_bytes", 0)
                                        for o in ops),
                "segments": segments,
                "removed_collective_bytes": sum(
                    s["removed_collective_bytes"] for s in segments),
            }
        if include_backward:
            bwd = self.backward()
            report["backward"] = {
                "cost": bwd.cost,
                "n_operators": len(bwd.eplan.specs),
                "operators": bwd.fused_signatures(),
            }
        return report

    def compile(self, pallas: Optional[str] = None,
                staged: Optional[bool] = None) -> "Compiled":
        """Stage 3: bind the plan to generated operators.

        ``pallas`` overrides the context's kernel-lowering policy:
        ``"never"`` (XLA-fused trace, the default), ``"interpret"``
        (Pallas template kernels in interpreter mode — CPU-safe
        validation), or ``"tpu"``.  With ``staged=True`` (default) the
        *whole plan* is compiled into a single jitted computation — one
        dispatch per call, literals folded as constants, dead
        intermediates freed for buffer reuse, distributed segments
        lowered into single ``shard_map`` regions — memoized in the
        structural whole-plan cache (:func:`whole_plan_cache_stats`);
        ``staged=False`` keeps per-operator dispatch as a debug path.
        Generated operators come from the global structural plan cache
        (:func:`plan_cache_stats`), so structurally-equal plans —
        retraced shapes, other expressions with the same skeleton —
        reuse compiled operators.  The returned :class:`Compiled` is
        callable on arrays and differentiable (``jax.custom_vjp`` whose
        backward is the *planned* gradient DAG)."""
        ctx = self.context
        if pallas is not None:
            ctx = ctx.with_(pallas=pallas)
        if staged is not None:
            ctx = ctx.with_(staged=staged)
        if ctx.verify != "off":
            # the compile() stage boundary re-checks the execution-level
            # invariants (liveness, aliasing, whole-plan key): the plan
            # object is mutable between stages
            report = VerifyReport(level=ctx.verify)
            report.diagnostics.extend(verify_exec(
                self.eplan, strict=ctx.verify == "strict",
                pallas=ctx.pallas, layout=ctx.layout))
            report.raise_if_errors()
        return Compiled(replace(self, context=ctx, _bwd=dict(self._bwd)))


# --------------------------------------------------------------------------
# stage 3: Compiled — an executable, differentiable fused operator
# --------------------------------------------------------------------------

class Compiled:
    """Executable fused operator: runs the CompiledPlan, constrains operand
    layouts, and registers a ``jax.custom_vjp`` whose backward pass is the
    planned gradient DAG."""

    def __init__(self, planned: Planned):
        self.planned = planned
        ctx = planned.context
        self.staged = ctx.staged
        self._cplan: CompiledPlan = compile_plan(
            planned.eplan, pallas=ctx.pallas, layout=ctx.layout,
            staged=ctx.staged, strict=ctx.verify == "strict",
            name=obs.program_name(planned.traced.name))
        self._n_outs = len(planned.eplan.graph.outputs)
        self._vjp_fn = None
        #: compiled backward per frozenset of differentiated input names
        self._bwd_compiled: dict[frozenset, CompiledPlan] = {}

    # -- serving hooks ------------------------------------------------------
    @property
    def input_order(self) -> list[str]:
        """Operand names in the staged function's positional order
        (``graph.inputs()`` order — may differ from the expression
        function's signature order)."""
        return [n.name for n in self.planned.eplan.graph.inputs()]

    def plan_key(self) -> tuple:
        """Structural whole-plan signature of this compiled plan (the
        mesh-free staged cache key).  Two Compiled objects with equal
        plan keys share one staged function and one XLA executable — the
        bucketing identity the fused-plan server
        (:mod:`repro.serve.fusion`) batches concurrent requests by."""
        from .codegen import staged_plan_key
        return staged_plan_key(self.planned.eplan,
                               pallas=self.planned.context.pallas)

    def batched(self):
        """Jitted vmapped form of the staged whole-plan function: takes
        each input stacked to ``(B, *shape)`` in :attr:`input_order` and
        returns the output tuple stacked the same way (batch elements
        independent).  Mesh-free dense plans only; shared across
        structurally-equal plans via the whole-plan cache."""
        return self._cplan.batched_callable()

    # -- execution ----------------------------------------------------------
    def _run_plain(self, bound: dict):
        lay = self.planned.context.layout
        if lay is not None:
            bound = {n: lay.apply(n, v) for n, v in bound.items()}
        outs = self._cplan(bound)
        if lay is not None:
            if isinstance(outs, tuple):
                outs = tuple(lay.apply(f"__out{i}", o)
                             for i, o in enumerate(outs))
            else:
                outs = lay.apply("__out0", outs)
        return outs

    def _get_bwd(self, wrt=None) -> tuple[CompiledPlan, list[str],
                                          list[str]]:
        """(compiled backward, the gradients it returns, its cotangent
        input names) for the inputs named in ``wrt`` (None: every input).
        Each differentiated set compiles its own program, all named
        ``plan_<region>_bwd``."""
        bwd = self.planned.backward(wrt)
        key = frozenset(bwd.grad_names)  # type: ignore[attr-defined]
        cp = self._bwd_compiled.get(key)
        if cp is None:
            cp = self._bwd_compiled[key] = compile_plan(
                bwd.eplan, pallas=self.planned.context.pallas,
                layout=self.planned.context.layout, staged=self.staged,
                name=obs.program_name(self.planned.traced.name,
                                      backward=True))
        ct_names = [n for n in bwd.traced.in_names if n.startswith("__ct")]
        return cp, bwd.grad_names, ct_names  # type: ignore[attr-defined]

    def _build_vjp(self):
        """The ``custom_vjp`` every dense call runs through.  ``fwd`` reads
        which inputs JAX perturbs (static at trace time) and hands the
        set to ``bwd`` in the residuals' tree structure; ``bwd`` runs the
        backward planned for that set only and returns None (a zero
        cotangent) for every other input."""
        import jax
        from jax.custom_derivatives import SymbolicZero
        names = list(self.planned.traced.in_names)
        graph_inputs = {n.name for n in self.planned.eplan.graph.inputs()}

        def run(*arrs):
            return self._run_plain(dict(zip(names, arrs)))

        @jax.custom_vjp
        def call(*arrs):
            return run(*arrs)

        def fwd(*primals):
            arrs = tuple(p.value for p in primals)
            # residuals: the primal inputs, and the perturbed names as
            # dict keys, which the residual tree carries statically
            wrt = {n: None for n, p in zip(names, primals) if p.perturbed}
            return run(*arrs), (arrs, wrt)

        def bwd(res, ct):
            arrs, wrt = res
            planned = [n for n in names if n in wrt and n in graph_inputs]
            if not planned:
                return (None,) * len(names)
            with obs.span(obs.CALL):
                if len(planned) < len(graph_inputs):
                    obs.count(obs.BWD_PRUNED)
                bwd_plan, grad_names, ct_names = self._get_bwd(planned)
                cts = ct if isinstance(ct, (tuple, list)) else (ct,)
                binds = dict(zip(names, arrs))
                binds.update({n: jnp.zeros(c.shape, jnp.float32)
                              if isinstance(c, SymbolicZero)
                              else jnp.asarray(c, jnp.float32)
                              for n, c in zip(ct_names, cts)})
                grads = bwd_plan(binds)
                if not isinstance(grads, tuple):
                    grads = (grads,)
                by_name = dict(zip(grad_names, grads))
                return tuple(by_name.get(n) for n in names)

        call.defvjp(fwd, bwd, symbolic_zeros=True)
        return call

    # -- calling ------------------------------------------------------------
    def explain(self, include_backward: bool = False) -> dict:
        report = self.planned.explain(include_backward=include_backward)
        # merge runtime-recorded downgrades (value-format decisions made
        # at call time) with the static ones, deduped by site+reason
        static = report["execution"]["fallbacks"]
        seen = {(f["site"], f["reason"]) for f in static}
        for f in self._cplan.fallbacks:
            if (f["site"], f["reason"]) not in seen:
                static.append(dict(f))
        if "row_orientation" in report["execution"]:
            # what the last call chose, per kernel
            report["execution"]["row_orientation"] = \
                self._cplan.row_orientation
        for bwd in self._bwd_compiled.values():
            seen = {(f["site"], f["reason"]) for f in static}
            for f in bwd.fallbacks:
                if (f["site"], f["reason"]) not in seen:
                    static.append(dict(f))
        return report

    def _bind(self, args, kwargs) -> dict:
        bound = dict(zip(self.planned.traced.in_names, args))
        bound.update(kwargs)
        return bound

    def __call__(self, *args, **kwargs):
        """Execute on concrete operands (positional or by name).

        Dense calls run through the ``custom_vjp`` wrapper, so the result
        is ``jax.grad``-able; calls with sparse/compressed operands take
        the direct dispatch path.  Any 1-D/0-D operand puts the call in
        "vector world": outputs round-trip back through
        :func:`_uncanon_output`."""
        with obs.span(obs.CALL):
            bound = self._bind(args, kwargs)
            vector_world = any(
                _canon_shape(n, v)[1] < 2 for n, v in bound.items())
            canon = {n: _canon_value(n, v) for n, v in bound.items()}
            dense = all(not isinstance(v, (BCSR, DictCompressed))
                        for v in canon.values())
            if dense:
                if self._vjp_fn is None:
                    self._vjp_fn = self._build_vjp()
                names = self.planned.traced.in_names
                outs = self._vjp_fn(*[canon[n] for n in names])
            else:
                outs = self._run_plain(canon)
            if vector_world:
                if isinstance(outs, tuple):
                    return tuple(_uncanon_output(o) for o in outs)
                return _uncanon_output(outs)
            return outs


# --------------------------------------------------------------------------
# the @fused wrapper — sugar over trace → plan → compile
# --------------------------------------------------------------------------

class Fused:
    """Callable wrapper staging an expression function on demand.

    Each distinct (shape, format, context) signature is traced, planned,
    and compiled once; subsequent calls reuse the Compiled stage (and,
    transitively, the structural plan cache)."""

    def __init__(self, fn: Callable, sparsity: Optional[dict] = None):
        self.fn = fn
        self.sparsity = dict(sparsity or {})
        self.names = list(inspect.signature(fn).parameters)
        self._staged: dict[tuple, Compiled] = {}

    # -- staged entry points ------------------------------------------------
    def trace(self, *args, **kwargs) -> Traced:
        """Stage 1: trace with abstract or concrete operands (anything with
        ``.shape`` — arrays, ShapeDtypeStructs, BCSR — or python scalars)."""
        bound = dict(zip(self.names, args))
        bound.update(kwargs)
        with obs.span(obs.PLAN_TRACE):
            exprs = _as_expr_inputs(bound, self.sparsity)
            outs = self.fn(**exprs)
            if not isinstance(outs, (tuple, list)):
                outs = (outs,)
            graph = ir.Graph.build(list(outs))
        meta = {}
        for name, v in bound.items():
            shape, _ = _canon_shape(name, v)
            fmt = ("bcsr" if isinstance(v, BCSR) else
                   "dict" if isinstance(v, DictCompressed) else "dense")
            meta[name] = {"shape": shape, "format": fmt,
                          "sparsity": exprs[name].node.sparsity}
        return Traced(getattr(self.fn, "__name__", "<expr>"), graph,
                      list(bound), meta)

    def plan_for(self, **shaped_args) -> ExecPlan:
        """Trace + plan under the current context (inspection helper)."""
        return self.trace(**shaped_args).plan().eplan

    # -- call sugar ---------------------------------------------------------
    def __call__(self, *args, **kwargs):
        ctx = current_context()
        bound = dict(zip(self.names, args))
        bound.update(kwargs)
        key = _signature(bound, ctx)
        compiled = self._staged.get(key)
        if compiled is None:
            compiled = self.trace(**bound).plan(context=ctx).compile()
            self._staged[key] = compiled
        return compiled(**bound)


def fused(fn: Optional[Callable] = None, *, sparsity: Optional[dict] = None):
    """Wrap an expression function as a stageable fused region.

    ``fn`` is a python function over :mod:`repro.core.ir` expressions
    (operands arrive as IR matrices; ``+ * @ .sum() ir.relu …`` build the
    HOP DAG).  The returned :class:`Fused` wrapper offers two spellings
    of the same pipeline:

    * **staged** — ``f.trace(*operands)`` → :class:`Traced`, then
      ``.plan(mode=, params=, layout=)`` → :class:`Planned`, then
      ``.compile(pallas=)`` → :class:`Compiled`, each stage inspectable
      (``Planned.explain()`` is the cost report);
    * **call sugar** — ``f(*arrays)`` traces/plans/compiles on first use
      per (shape, format, context) signature and memoizes the Compiled
      stage.

    Operands may be 2-D matrices (dense, ``BCSR``, ``DictCompressed``),
    1-D vectors, or 0-D scalars — see :func:`_canon_shape` for the
    canonicalization and round-trip rule.  ``sparsity`` optionally maps
    operand names to assumed densities for planning.

    Usable bare (``@fused``) or with arguments
    (``@fused(sparsity={"X": 0.05})``).
    """
    if fn is None:
        return lambda f: Fused(f, sparsity=sparsity)
    return Fused(fn, sparsity=sparsity)


def fuse_exprs(outputs, bindings: dict[str, object],
               mode: Optional[str] = None):
    """One-shot: plan + execute a hand-built expression DAG (honors the
    scoped context's layout the same way the staged path does)."""
    ctx = current_context()
    if mode is not None:
        ctx = ctx.with_(mode=mode)
    graph = ir.Graph.build(outputs if isinstance(outputs, (list, tuple))
                           else [outputs])
    if ctx.layout is not None and not isinstance(ctx.layout, FusionLayout):
        ctx = ctx.with_(layout=ensure_layout(ctx.layout, graph))
    eff = layout_cost_params(ctx.layout, graph, ctx.params)
    eplan = plan_graph(graph, ctx.mode, eff)
    if ctx.layout is not None:
        bindings = {n: ctx.layout.apply(n, v) for n, v in bindings.items()}
    outs = compile_plan(eplan, pallas=ctx.pallas, layout=ctx.layout,
                        staged=ctx.staged)(bindings)
    if ctx.layout is not None:
        if isinstance(outs, tuple):
            outs = tuple(ctx.layout.apply(f"__out{i}", o)
                         for i, o in enumerate(outs))
        else:
            outs = ctx.layout.apply("__out0", outs)
    return outs
