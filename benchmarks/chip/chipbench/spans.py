"""Host spans from the benchmark's own files, and JAX's compile events.

With tracing on, each span is a ``jax.profiler.TraceAnnotation`` and so
lands in the profiler's trace on the device's clock; with it off a span
costs nothing.  Spans named in ``timed`` are also summed on the host
clock whether or not the profiler runs (set-up phases such as planning).
"""

from __future__ import annotations

import contextlib
import threading
import time


class Spans:
    def __init__(self, trace: bool):
        self.trace = trace
        self.seconds: dict[str, float] = {}
        self._lock = threading.Lock()

    def span(self, name: str):
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    @contextlib.contextmanager
    def timed(self, name: str):
        """A span that is also summed on the host clock."""
        t0 = time.perf_counter()
        try:
            with self.span(name):
                yield
        finally:
            dt = time.perf_counter() - t0
            with self._lock:
                self.seconds[name] = self.seconds.get(name, 0.0) + dt


class CompileMonitor:
    """Sums JAX's own compile events by phase (``setup``, ``window``,
    ``after``): backend compiles and their seconds, persistent-cache
    retrievals and their seconds, and lowerings (every compile request,
    served from a cache or not)."""

    _BACKEND = "/jax/core/compile/backend_compile_duration"
    _LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    _RETRIEVE = "/jax/compilation_cache/cache_retrieval_time_sec"

    def __init__(self):
        self.phase = "setup"
        self.by_phase: dict[str, dict] = {}
        self._lock = threading.Lock()
        self._installed = False

    def install(self) -> "CompileMonitor":
        import jax.monitoring as mon
        if not self._installed:
            mon.register_event_duration_secs_listener(self._on_duration)
            self._installed = True
        return self

    def uninstall(self) -> None:
        # jax keeps listeners in a module-level list; take ours out so a
        # second run in one process (the tests) does not count twice
        import jax.monitoring as mon
        if self._installed:
            mon.unregister_event_duration_listener(self._on_duration)
            self._installed = False

    @staticmethod
    def _zero() -> dict:
        return {"backend_compiles": 0, "backend_compile_s": 0.0,
                "cache_retrievals": 0, "cache_retrieval_s": 0.0,
                "lowerings": 0}

    def _on_duration(self, event: str, secs: float, **_kw) -> None:
        with self._lock:
            r = self.by_phase.setdefault(self.phase, self._zero())
            if event == self._BACKEND:
                r["backend_compiles"] += 1
                r["backend_compile_s"] += secs
            elif event == self._RETRIEVE:
                r["cache_retrievals"] += 1
                r["cache_retrieval_s"] += secs
            elif event == self._LOWER:
                r["lowerings"] += 1

    def get(self, phase: str) -> dict:
        with self._lock:
            return dict(self.by_phase.get(phase) or self._zero())


@contextlib.contextmanager
def planning_span(spans: Spans):
    """Time the program's planning (``Traced.plan`` and the planned
    backward, ``Planned.backward``) under the span ``bench.plan`` while
    the body runs.  Nested planning counts once."""
    from repro.core import api

    depth = threading.local()
    saved = {(cls, name): getattr(cls, name)
             for cls, name in ((api.Traced, "plan"),
                               (api.Planned, "backward"))}

    def wrap(fn):
        def timed(*a, **kw):
            if getattr(depth, "n", 0):
                return fn(*a, **kw)
            depth.n = 1
            try:
                with spans.timed("bench.plan"):
                    return fn(*a, **kw)
            finally:
                depth.n = 0
        return timed

    for (cls, name), fn in saved.items():
        setattr(cls, name, wrap(fn))
    try:
        yield
    finally:
        for (cls, name), fn in saved.items():
            setattr(cls, name, fn)
