"""Program spans and the names of the kernels the program generates.

``span(name)`` marks one stretch of host work.  Each span is a
``jax.profiler.TraceAnnotation``, so under the profiler it lands on the
host plane on the same clock as the device's operations, and each is also
summed, seconds and count per name on the host clock, into one
process-wide table that :func:`snapshot` reads.  The table is
process-wide because the spans sit deep inside the planner, the code
generator and the fit loops, where no caller's object reaches.  A span
costs about a microsecond with the profiler off, so it has no switch.

A span opened while a span of the same name is already open on the
same thread counts once: only the outermost is annotated and summed
(its ``seconds`` are still measured, for callers that keep their own
totals).

Span names, all under :data:`PREFIX`:

* ``repro.plan``: planning one region, forward (``Traced.plan``) or
  planned backward (``Planned.backward``); its phases
  ``repro.plan.trace`` (``Fused.trace``), ``repro.plan.rewrite`` (the
  variant sweep, whose variants' explore and select nest in it),
  ``repro.plan.explore``, ``repro.plan.select`` and ``repro.plan.verify``;
* ``repro.codegen``: building a fused operator's CPlan or a staged
  whole-plan function;
* ``repro.stage``: the first call of a staged plan at a new signature,
  where JAX traces it, lowers it (the Pallas kernels through Mosaic
  included) and compiles it or loads it from the compilation cache;
* ``repro.call``: the host binding, canonicalising and dispatching one
  fused region (``Compiled.__call__`` and the planned backward's
  dispatch in the custom VJP);
* ``repro.sync``: a blocking device-to-host read of the fit loops;
* ``repro.grad``: one value-and-gradient call of the MLogReg fit (the
  fused forward's and the planned backward's dispatch, and JAX's
  autodiff glue between them);
* ``repro.cg``: one inner conjugate-gradient step of the MLogReg fit
  (the Hessian-vector product's dispatch, its two reads and the eager
  vector updates).

Counts (:func:`count`) share the table, with no seconds:

* ``repro.kernel.row_lanes``: a Row kernel lowered in its lane-major
  orientation (:mod:`repro.kernels.rowwise`), once per lowering;
* ``repro.bwd.pruned``: a planned backward run for fewer inputs than its
  region has, because JAX differentiates only those
  (``Compiled._build_vjp``), once per backward call.

Kernel names (:func:`kernel_name`) have the form
``<template>_<variant>_<plan digest>`` and are given to every
``pallas_call``, so a device trace names each generated kernel; a
lane-major Row kernel's template token is ``rowt``.

Program names (:func:`program_name`) name each staged whole-plan
program's XLA module after the fused region it runs (``plan_<region>``,
``plan_<region>_bwd`` for the region's planned backward), so a device
trace attributes every operation of a program, XLA fusions included, to
its region: the trace's ``XLA Modules`` events read ``jit_<name>(...)``.
Structurally equal plans share one program (``WholePlanCache``), which
keeps the name of the region that built it first.
"""

from __future__ import annotations

import re
import threading
import time

from jax.profiler import TraceAnnotation

PREFIX = "repro."
PLAN = "repro.plan"
PLAN_TRACE = "repro.plan.trace"
PLAN_REWRITE = "repro.plan.rewrite"
PLAN_EXPLORE = "repro.plan.explore"
PLAN_SELECT = "repro.plan.select"
PLAN_VERIFY = "repro.plan.verify"
CODEGEN = "repro.codegen"
STAGE = "repro.stage"
CALL = "repro.call"
SYNC = "repro.sync"
GRAD = "repro.grad"
CG = "repro.cg"
ROW_LANES = "repro.kernel.row_lanes"
BWD_PRUNED = "repro.bwd.pruned"

_lock = threading.Lock()
_table: dict[str, list] = {}          # name -> [seconds, count]
_kernels: set[str] = set()
_programs: set[str] = set()
_local = threading.local()            # names of the spans open per thread


class span:
    """Context manager for one span; ``seconds`` holds its duration once
    it has closed."""

    __slots__ = ("name", "seconds", "_t0", "_ann")

    def __init__(self, name: str):
        self.name = name
        self.seconds = 0.0
        self._ann = None

    def __enter__(self) -> "span":
        open_ = getattr(_local, "open", None)
        if open_ is None:
            open_ = _local.open = set()
        if self.name not in open_:
            open_.add(self.name)
            self._ann = TraceAnnotation(self.name)
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self._t0
        if self._ann is None:
            return
        self._ann.__exit__(*exc)
        self._ann = None
        _local.open.discard(self.name)
        with _lock:
            rec = _table.get(self.name)
            if rec is None:
                _table[self.name] = [self.seconds, 1]
            else:
                rec[0] += self.seconds
                rec[1] += 1


def count(name: str) -> None:
    """Add one to the count of ``name`` in the table (no seconds)."""
    with _lock:
        rec = _table.get(name)
        if rec is None:
            _table[name] = [0.0, 1]
        else:
            rec[1] += 1


def snapshot() -> dict[str, dict]:
    """``{name: {"seconds": s, "count": n}}`` of every span closed so far
    in this process."""
    with _lock:
        return {k: {"seconds": s, "count": n} for k, (s, n) in _table.items()}


def kernel_name(template: str, variant: str, digest: str) -> str:
    """``<template>_<variant>_<first 8 hex of digest>``, registered as a
    kernel the program created."""
    name = f"{template}_{variant}_{digest[:8]}"
    with _lock:
        _kernels.add(name)
    return name


def kernel_names() -> frozenset[str]:
    """Every kernel name registered so far in this process."""
    with _lock:
        return frozenset(_kernels)


def program_name(region: str, backward: bool = False) -> str:
    """``plan_<region>`` (``_bwd`` appended for its planned backward), each
    character that is no letter, digit or ``_`` made ``_``; registered,
    as :func:`kernel_name` registers a kernel's."""
    name = "plan_" + re.sub(r"\W", "_", region) + ("_bwd" if backward
                                                   else "")
    with _lock:
        _programs.add(name)
    return name


def program_names() -> frozenset[str]:
    """Every program name registered so far in this process."""
    with _lock:
        return frozenset(_programs)
