"""JAX's own compile events, captured with their wall-clock times.

JAX records each trace, lowering, backend compile and persistent-cache
read as a ``jax.monitoring`` duration event. The harness keeps them so a
window can say how much host time went to building programs inside it
(``walk.jit_s_per_block``), whether anything compiled there, and what
the host was doing in an idle gap of the trace.
"""
from __future__ import annotations

import time
from typing import List, Tuple

import jax.monitoring

# The four durations that together are "building a program": tracing to a
# jaxpr, lowering to MLIR, the backend compile, and a read from the
# persistent compilation cache in its place.
BUILD_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
    "/jax/compilation_cache/cache_retrieval_time_sec",
)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Monitor:
    """Collects (event, start, end) in ``time.time()`` seconds. The end is
    when JAX reported the event, the start that minus its duration."""

    def __init__(self):
        self.events: List[Tuple[str, float, float]] = []
        self._on = True
        jax.monitoring.register_event_duration_secs_listener(self._record)

    def _record(self, event: str, duration: float, **_kw) -> None:
        if self._on and event in BUILD_EVENTS:
            end = time.time()
            self.events.append((event, end - float(duration), end))

    def close(self) -> None:
        self._on = False
        jax.monitoring.unregister_event_duration_listener(self._record)

    def between(self, t0: float, t1: float) -> List[Tuple[str, float, float]]:
        """Events that ended inside [t0, t1] (``time.time()`` seconds)."""
        return [e for e in self.events if t0 <= e[2] <= t1]

    def build_seconds(self, t0: float, t1: float) -> float:
        return sum(end - start for _, start, end in self.between(t0, t1))

    def compiles(self, t0: float, t1: float) -> int:
        return sum(1 for name, _, _ in self.between(t0, t1)
                   if name == COMPILE_EVENT)
