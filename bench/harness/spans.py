"""The program's own spans in a profiler trace, against the chip's idle time.

The program enters a profiler annotation for each of its spans
(``repro.obs.trace``): the walk's phases ``walk/setup``, ``walk/teacher``,
``walk/tune`` and ``walk/student`` inside ``ebft/walk``, and serving's
``serve/admit`` and ``serve/step``, with ``serve/sync`` inside each step.
This reduces the run's ``.xplane.pb`` to:

* the program's spans inside the ``bench.window`` annotation, by name,
  with their attributes, each with its parent: the innermost program span
  that encloses it on the same thread;
* chip 0's busy intervals in the window, defined as ``xplane.reduce``
  defines busy (the union of the "XLA Modules" executions), put on the
  host's clock;
* JAX's program-build events on the trace's clock (the harness's
  monitor, converted as ``cli._on_trace_clock`` converts them).

The profiler stamps device events on the chip's clock and host events on
the host's, and the two can sit up to a millisecond apart, by an amount
that drifts within a run (recorded on a v5e: programs appear to start
0.3-1.4 ms before the host enqueued them, more in a run's first second).
Over a decode step of a few milliseconds that would move idle time from
one span into its neighbour, so each group of ``GROUP`` consecutive
programs of chip 0 is shifted by the smallest offset under which none of
them starts before the host began to enqueue it (host
``DoEnqueueProgram`` and device execution matched by ``run_id``); a group
with no such pair takes the offset of the group before it, or 0.

A per-layer metric reader calls :func:`of` with the run it reads; a
program without these spans gives an empty list of spans, and the
readers report nothing.
"""
from __future__ import annotations

import bisect
import dataclasses
import statistics
import sys
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

from harness import xplane

PROGRAM = ("ebft/", "walk/", "serve/")   # the program's span namespaces
ENQUEUE = "DoEnqueueProgram"
# JAX lowers each program it builds once; a jitted library function met
# while tracing a program is traced again but lowered only inside it
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
GROUP = 256  # consecutive programs of chip 0 that share one clock offset

Interval = Tuple[float, float]


@dataclasses.dataclass
class Span:
    name: str
    start: float                  # ns, host clock of the trace
    end: float
    attrs: Dict[str, Any]
    thread: str                   # the host line the span was recorded on
    parent: Optional[int] = None  # index into Spans.spans


@dataclasses.dataclass
class Spans:
    window: Interval
    spans: List[Span]
    busy: List[Interval]          # chip 0, merged, window-clipped, host clock
    builds: List[Tuple[str, float, float]]  # (event, start, end) in ns
    offset_ns: float              # added to device times (median over groups)

    def __post_init__(self):
        self._starts = [a for a, _ in self.busy]
        self._cum = [0.0]
        for a, b in self.busy:
            self._cum.append(self._cum[-1] + (b - a))
        self._kids: Dict[int, List[Span]] = {}
        for s in self.spans:
            if s.parent is not None:
                self._kids.setdefault(s.parent, []).append(s)

    def named(self, *names: str) -> List[Span]:
        return [s for s in self.spans if s.name in names]

    def children(self, k: int, *names: str) -> List[Span]:
        """The spans called one of ``names`` whose parent is span ``k``."""
        return [s for s in self._kids.get(k, ()) if s.name in names]

    def busy_ns(self, a: float, b: float) -> float:
        """Chip busy time inside [a, b] (ns)."""
        if b <= a or not self.busy:
            return 0.0
        i = max(bisect.bisect_right(self._starts, a) - 1, 0)
        j = bisect.bisect_left(self._starts, b)   # intervals i..j-1 may overlap
        if j <= i:
            return 0.0
        total = self._cum[j] - self._cum[i]
        total -= max(0.0, min(a, self.busy[i][1]) - self.busy[i][0])
        total -= max(0.0, self.busy[j - 1][1] - max(b, self.busy[j - 1][0]))
        return max(total, 0.0)

    def idle_ns(self, span: Span) -> float:
        """Chip idle time inside ``span``, within the window (ns)."""
        a, b = max(span.start, self.window[0]), min(span.end, self.window[1])
        return max(b - a, 0.0) - self.busy_ns(a, b) if b > a else 0.0

    def idle_s(self, names: Sequence[str], less: Sequence[str] = ()) -> float:
        """Idle seconds inside the spans called ``names``, less the idle
        inside their children called ``less``."""
        total = 0.0
        for k, s in enumerate(self.spans):
            if s.name in names:
                total += self.idle_ns(s)
                total -= sum(self.idle_ns(c) for c in self.children(k, *less))
        return total * 1e-9

    def builds_in(self, names: Sequence[str], event: str = LOWER_EVENT) -> int:
        """JAX ``event`` events that ended inside a span called ``names``."""
        spans = sorted((s.start, s.end) for s in self.spans if s.name in names)
        n = 0
        for name, _, end in self.builds:
            if name != event:
                continue
            k = bisect.bisect_right(spans, (end, float("inf"))) - 1
            n += k >= 0 and spans[k][0] <= end <= spans[k][1]
        return n


def _stats(ev) -> Dict[str, Any]:
    return {k: v for k, v in ev.stats}


def on_host_clock(runs: Sequence[Tuple[float, float, Optional[int]]],
                  enqueued: Dict[int, float]) -> Tuple[List[Interval], List[float]]:
    """Device executions (start, end, run_id) shifted onto the host's
    clock, in time order, and the offset of each group that had a pair;
    ``enqueued``: run_id -> when the host began to enqueue it."""
    shifted: List[Interval] = []
    offsets: List[float] = []
    offset = 0.0
    runs = sorted(runs, key=lambda r: r[0])
    for k in range(0, len(runs), GROUP):
        group = runs[k:k + GROUP]
        lags = [enqueued[r] - a for a, _, r in group if r in enqueued]
        if lags:
            offset = max(lags)
            offsets.append(offset)
        shifted += [(a + offset, b + offset) for a, b, _ in group]
    return shifted, offsets


def reduce(path: str, events: Iterable[Tuple[str, float, float]] = (),
           wall0: float = 0.0) -> Spans:
    """``events``: JAX's build events (event, start, end) in ``time.time()``
    seconds, as the harness's monitor holds them; the window annotation
    opened at ``wall0`` on that clock."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    planes = list(pd.planes)
    window = None
    raw: List[Span] = []
    enqueued: Dict[int, float] = {}
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                name = ev.name
                if name == xplane.WINDOW and window is None:
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                elif name.startswith(PROGRAM):
                    raw.append(Span(name, ev.start_ns, ev.start_ns + ev.duration_ns,
                                    _stats(ev), f"{plane.name}/{line.name}"))
                elif name == ENQUEUE:
                    st = _stats(ev)
                    if "run_id" in st and st.get("device_ordinal", 0) == 0:
                        enqueued.setdefault(int(st["run_id"]), ev.start_ns)
    if window is None:
        raise ValueError(f"no {xplane.WINDOW} annotation in {path}")
    chips = sorted((p for p in planes if xplane.DEVICE_PLANE.match(p.name)),
                   key=lambda p: p.name)
    runs: List[Tuple[float, float, Optional[int]]] = []
    if chips:
        for line in chips[0].lines:
            if line.name == xplane.MODULES_LINE:
                for ev in line.events:
                    rid = _stats(ev).get("run_id")
                    runs.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                 None if rid is None else int(rid)))
    w0, w1 = window
    shifted, offsets = on_host_clock(runs, enqueued)
    busy = xplane.union([iv for a, b in shifted if (iv := xplane._clip(a, b, w0, w1))])
    inside = sorted((s for s in raw if s.end > w0 and s.start < w1),
                    key=lambda s: (s.thread, s.start, -s.end))
    open_: Dict[str, List[int]] = {}
    for k, s in enumerate(inside):
        stack = open_.setdefault(s.thread, [])
        while stack and inside[stack[-1]].end < s.end:
            stack.pop()
        s.parent = stack[-1] if stack else None
        stack.append(k)
    builds = sorted(((name, w0 + (a - wall0) * 1e9, w0 + (b - wall0) * 1e9)
                     for name, a, b in events), key=lambda e: e[2])
    return Spans(window=window, spans=inside, busy=busy, builds=builds,
                 offset_ns=statistics.median(offsets) if offsets else 0.0)


def of(run) -> Optional[Spans]:
    """The spans of the traced run that ``run`` (a ``cli.RunView``) shows,
    reduced once and kept on it as ``run.spans``; None where the run has
    no trace to read. The view carries no trace path, so the reduction
    finds it in the frame of ``cli.run_cell`` that built the view."""
    if hasattr(run, "spans"):
        return run.spans
    found = None
    frame = sys._getframe(1)
    while frame is not None:
        loc = frame.f_locals
        if loc.get("view") is run and {"trace_dir", "mon", "wall0"} <= set(loc):
            found = reduce(xplane.find_trace(loc["trace_dir"]), loc["mon"].events,
                           loc["wall0"])
            break
        frame = frame.f_back
    run.spans = found
    return found

