"""Span tracer: nested host-time spans that are also profiler annotations.

Every :func:`span` enters a ``jax.profiler.TraceAnnotation`` of the same
name and attributes, so under a profiler session (``jax.profiler.trace``
or ``start_trace``) the span is a host event on the device trace's clock,
beside the programs it dispatched; with no session the annotation records
nothing and costs about a microsecond. Attributes carry identifiers
(``block=i``, ``uid=``), never values that need a device sync.

A live :class:`Span` additionally measures host wall-time between
``__enter__`` and ``__exit__`` on the monotonic clock. jax dispatch is
async, so that is host time: the device time of the work a span launched
comes from the profiler trace. ``sp.fence(value)`` blocks on ``value``
inside the span, for callers that want a span to wait for its device work
(a pipeline phase, a kernel timing); the walk and serving never fence.

The module-level :func:`span` dispatches to the current tracer: a
:class:`NullTracer` by default, whose spans wrap only the annotation, or a
live :class:`Tracer` installed by ``start_run`` (repro.obs.run), which
also keeps the span tree and calls its JSONL emitters at every span end.
While a run is live, :meth:`Tracer.book_build` (a ``jax.monitoring``
listener ``start_run`` installs) adds JAX's own program-build durations
to the innermost open span as ``build_s``, and counts the programs built
(lowered) as ``builds``.

Spans must be strictly nested (they form a tree); the tracer keeps the
open-span stack and the list of completed roots. ``Tracer.tree()``
returns the JSON-ready forest the report CLI renders.
"""
from __future__ import annotations

import time
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

from jax.profiler import TraceAnnotation

# JAX's duration events that together are building a program: tracing to
# a jaxpr, lowering to MLIR, the backend compile, or a read from the
# persistent compilation cache in its place. Lowering happens once per
# program built (a jitted function traced inside another is traced again,
# but lowered only as part of the outer program), so it counts builds.
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BUILD_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    LOWER_EVENT,
    "/jax/core/compile/backend_compile_duration",
    "/jax/compilation_cache/cache_retrieval_time_sec",
)


class Span(TraceAnnotation):
    """One timed region. Context manager; re-entry is not supported."""

    __slots__ = ("name", "attrs", "start", "duration", "children", "_tracer")

    def __init__(self, name: str, attrs: Dict[str, Any], tracer: "Tracer"):
        super().__init__(name, **attrs)
        self.name = name
        self.attrs = attrs
        self.start: float = 0.0
        self.duration: float = 0.0
        self.children: List["Span"] = []
        self._tracer = tracer

    def set(self, **attrs) -> "Span":
        """Attach attributes discovered while the span is open (kept in
        the span tree; the profiler event keeps those given at opening)."""
        self.attrs.update(attrs)
        return self

    def fence(self, value):
        """Block until ``value``'s device work is done; returns ``value``.

        Puts the async dispatch inside this span's wall-time.
        """
        import jax

        jax.block_until_ready(value)
        return value

    # -- context manager ------------------------------------------------
    def __enter__(self) -> "Span":
        super().__enter__()
        self._tracer._push(self)
        self.start = self._tracer.clock()
        return self

    def __exit__(self, *exc) -> bool:
        self.duration = self._tracer.clock() - self.start
        self._tracer._pop(self)
        super().__exit__(*exc)
        return False

    def asdict(self) -> Dict[str, Any]:
        d: Dict[str, Any] = {
            "name": self.name,
            "start": self.start,
            "duration_s": self.duration,
        }
        if self.attrs:
            d["attrs"] = dict(self.attrs)
        if self.children:
            d["children"] = [c.asdict() for c in self.children]
        return d


class _NullSpan(TraceAnnotation):
    """The profiler annotation alone: no clock, no tree, no state."""

    __slots__ = ()
    name = ""
    attrs: Dict[str, Any] = {}
    start = 0.0
    duration = 0.0
    children: List = []

    def set(self, **attrs):
        return self

    def fence(self, value):
        return value


class Tracer:
    """Collects a forest of completed spans; emits span-end events."""

    enabled = True

    def __init__(self, clock: Callable[[], float] = time.perf_counter):
        self.clock = clock
        self.roots: List[Span] = []
        self._stack: List[Span] = []
        self._emit: List[Callable[[Dict[str, Any]], None]] = []

    def span(self, name: str, **attrs) -> Span:
        return Span(name, attrs, self)

    def add_emitter(self, fn: Callable[[Dict[str, Any]], None]) -> None:
        """``fn(event_dict)`` is called at every span end (JSONL sinks)."""
        self._emit.append(fn)

    def book_build(self, event: str, duration: float, **_kw) -> None:
        """``jax.monitoring`` duration listener: books a program-build
        event onto the innermost open span (none open: dropped)."""
        if event in BUILD_EVENTS and self._stack:
            attrs = self._stack[-1].attrs
            attrs["build_s"] = attrs.get("build_s", 0.0) + float(duration)
            if event == LOWER_EVENT:
                attrs["builds"] = attrs.get("builds", 0) + 1

    # -- stack maintenance (called by Span) -----------------------------
    def _push(self, sp: Span) -> None:
        if self._stack:
            self._stack[-1].children.append(sp)
        else:
            self.roots.append(sp)
        self._stack.append(sp)

    def _pop(self, sp: Span) -> None:
        # tolerate exceptions unwinding several spans at once: pop to sp
        while self._stack:
            top = self._stack.pop()
            if top is sp:
                break
        if self._emit:
            ev = {
                "type": "span",
                "name": sp.name,
                "start": sp.start,
                "duration_s": sp.duration,
                "depth": len(self._stack),
            }
            if sp.attrs:
                ev["attrs"] = dict(sp.attrs)
            for fn in self._emit:
                fn(ev)

    def tree(self) -> List[Dict[str, Any]]:
        return [r.asdict() for r in self.roots]


class NullTracer:
    """Default tracer: observability off; spans are bare annotations."""

    enabled = False
    roots: List[Span] = []

    def span(self, name: str, **attrs) -> _NullSpan:
        return _NullSpan(name, **attrs)

    def add_emitter(self, fn) -> None:
        pass

    def tree(self) -> List[Dict[str, Any]]:
        return []


NULL_TRACER = NullTracer()
_TRACER: Any = NULL_TRACER


def get_tracer():
    return _TRACER


def set_tracer(tracer: Optional[Tracer]) -> None:
    """Install ``tracer`` as the process tracer (None restores the null)."""
    global _TRACER
    _TRACER = tracer if tracer is not None else NULL_TRACER


def span(name: str, **attrs):
    """Open a span on the current tracer (an annotation alone when off)."""
    return _TRACER.span(name, **attrs)


def enabled() -> bool:
    return _TRACER.enabled


def totals(forest: Iterable[Dict[str, Any]], name: str) -> Tuple[float, float]:
    """(seconds, build seconds) summed over every span called ``name`` in
    a ``Tracer.tree()`` forest; a span's build seconds include those its
    descendants booked. Spans of one name are assumed not to nest."""
    seconds = build = 0.0

    def subtree_build(node) -> float:
        return (node.get("attrs", {}).get("build_s", 0.0)
                + sum(subtree_build(c) for c in node.get("children", ())))

    stack = list(forest)
    while stack:
        node = stack.pop()
        if node["name"] == name:
            seconds += node["duration_s"]
            build += subtree_build(node)
        else:
            stack.extend(node.get("children", ()))
    return seconds, build
