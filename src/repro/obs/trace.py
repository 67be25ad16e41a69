"""Span tracer: explicit begin/end spans at host wave boundaries.

Why spans and not a sampling profiler: the wave loops in ``index/graph.py``
and ``index/ivf.py`` interleave device launches with host-side routing,
merging, and frontier exchange.  A sampling profiler attributes that time
to whatever Python frame it lands in; what the latency work needs is the
paper's own decomposition — route / stage-1 DMA / stage-2 / exchange /
merge / host-commit — measured per wave.  So the engines (and the
request scheduler) open explicit spans at those boundaries.

Every span reaches a running profiler capture (``jax.profiler.trace`` or
``start_trace``) as a ``jax.profiler.TraceAnnotation``: on the profiler's
clock, beside the device's ``XLA Ops`` line, and unfenced, so the capture
shows the host and the device as they overlap.  "Tracing on" means "a
capture is running"; nothing else needs switching.

Two tracers:

  * ``NULL_TRACER`` (the default): ``span`` returns a ``TraceAnnotation``
    while a capture runs, else one preallocated no-op context manager;
    ``fence``, ``instant`` and ``annotate`` are no-ops.  With no capture
    the instrumented code allocates nothing and tests no flag.
  * ``Tracer`` (``serve.py --trace``): additionally records each span and
    instant on ``perf_counter_ns`` for the Chrome-trace export, and its
    ``fence`` (``jax.block_until_ready``) makes those recorded edges cover
    the device work they name — without it async dispatch books every
    kernel's time to whichever span happens to materialise the array
    later.  The fences apply to the export only; a profiler capture needs
    none.

Enabling the recording tracer is swapping the module-level pointer
(``set_tracer``), nothing else; the engines never test a flag.

This module is dependency-free: jax is imported on the first ``span``
(and in ``Tracer.fence``), and without jax no capture can run, so the
registry/export half of obs works in plain-CPython contexts like the CI
schema check.
"""

from __future__ import annotations

import time

__all__ = ["Tracer", "NullTracer", "NULL_TRACER", "current_tracer",
           "set_tracer", "use_tracer"]


class _NullSpan:
    """Reusable no-op context manager — one instance for the whole process
    so the disabled step path allocates nothing."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def annotate(self, **args):
        pass


_NULL_SPAN = _NullSpan()


class _NoProfiler:
    """Stands in for ``TraceAnnotation`` where jax cannot be imported."""

    @staticmethod
    def is_enabled() -> bool:
        return False


_annotation_cls = None  # _Annotation (or _NoProfiler), on first use


def _annotation_class():
    """``TraceAnnotation`` with the span interface the engines use: its
    ``annotate`` attaches args to the captured event (``set_metadata``),
    as the recording tracer's attaches them to its own."""
    try:
        from jax.profiler import TraceAnnotation
    except ImportError:
        return _NoProfiler

    class _Annotation(TraceAnnotation):
        __slots__ = ()

        def annotate(self, **args):
            self.set_metadata(**args)

    return _Annotation


def _annotation(name: str, args: dict):
    """A ``TraceAnnotation`` for ``name`` while a profiler capture runs
    (the check costs about 50 ns), else the shared no-op span."""
    global _annotation_cls
    if _annotation_cls is None:
        _annotation_cls = _annotation_class()
    if _annotation_cls.is_enabled():
        return _annotation_cls(name, **args)
    return _NULL_SPAN


class NullTracer:
    """Default tracer: records nothing.  ``span`` reaches a running
    profiler capture and is otherwise the shared no-op singleton; the
    other operations are no-ops.  ``enabled`` (whether spans are recorded
    for export) lets rare non-hot-path code branch, but instrumented
    engine code must not — it just calls through."""

    __slots__ = ()
    enabled = False

    def span(self, name: str, **args):
        return _annotation(name, args)

    def instant(self, name: str, **args):
        pass

    def annotate(self, **args):
        pass

    def fence(self, value):
        return value


NULL_TRACER = NullTracer()


class _Span:
    __slots__ = ("_tracer", "name", "args", "_t0", "_ann")

    def __init__(self, tracer: "Tracer", name: str, args: dict):
        self._tracer = tracer
        self.name = name
        self.args = args
        self._t0 = 0
        self._ann = _NULL_SPAN

    def __enter__(self):
        self._ann = _annotation(self.name, self.args)
        self._ann.__enter__()
        self._tracer._stack.append(self)
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        end = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        tr = self._tracer
        popped = tr._stack.pop()
        if popped is not self:  # pragma: no cover - misuse guard
            raise RuntimeError(
                f"span nesting violated: exiting {self.name!r} but "
                f"innermost open span is {popped.name!r}")
        tr.events.append({
            "name": self.name, "ph": "X", "ts": self._t0, "dur": end - self._t0,
            "depth": len(tr._stack), "args": self.args,
        })
        return False

    def annotate(self, **args):
        self.args.update(args)


class Tracer:
    """Recording tracer.  Events accumulate as plain dicts (timestamps in
    perf_counter_ns ticks; export converts to Chrome-trace microseconds).
    Each span also reaches a running profiler capture, as on
    ``NullTracer``.

    Spans are strictly nested context managers; ``instant`` records a
    zero-duration annotation event at the current depth (used for per-wave
    byte attributions: stage-1 DMA, stage-2 slabs, exchange)."""

    __slots__ = ("events", "_stack", "meta")
    enabled = True

    def __init__(self, **meta):
        self.events: list[dict] = []
        self._stack: list[_Span] = []
        self.meta = dict(meta)

    def span(self, name: str, **args) -> _Span:
        return _Span(self, name, args)

    def instant(self, name: str, **args) -> None:
        self.events.append({
            "name": name, "ph": "i", "ts": time.perf_counter_ns(),
            "depth": len(self._stack), "args": args,
        })

    def annotate(self, **args) -> None:
        """Attach args to the innermost open span (no-op at top level, so
        shared helpers can annotate without knowing their call context)."""
        if self._stack:
            self._stack[-1].args.update(args)

    def fence(self, value):
        """Block until ``value``'s device computation is done, then return
        it — the honesty barrier for span timing.  jax is imported lazily
        so constructing/exporting traces never requires it."""
        import jax
        return jax.block_until_ready(value)

    def depth(self) -> int:
        return len(self._stack)


# ---------------------------------------------------------------------------
# Module-level current tracer.  Engines resolve it at call time via
# ``current_tracer()`` so a tracer installed by serve.py is seen by every
# layer without parameter threading.
# ---------------------------------------------------------------------------

_current: NullTracer | Tracer = NULL_TRACER


def current_tracer():
    return _current


def set_tracer(tracer) -> None:
    global _current
    _current = NULL_TRACER if tracer is None else tracer


class use_tracer:
    """Context manager installing ``tracer`` for the dynamic extent, always
    restoring the previous one (tests rely on this to not leak state)."""

    def __init__(self, tracer):
        self._tracer = tracer
        self._prev = None

    def __enter__(self):
        global _current
        self._prev = _current
        _current = NULL_TRACER if self._tracer is None else self._tracer
        return self._tracer

    def __exit__(self, *exc):
        global _current
        _current = self._prev
        return False
