"""Reduction of a profiler trace (``.xplane.pb``) to device metrics.

The harness marks its measured window with a host annotation
(``bench.window``) and each call into the program with ``bench.<what>``.
Within that window, per chip:

* busy: the union of the intervals in which a program ran on the chip
  (the "XLA Modules" line of the device plane), idle the rest;
* device time per program: the summed durations of its executions,
  under its program name with the ``jit_`` prefix and any id suffix cut;
* device time per operation: the "XLA Ops" line, summed by name;
* the longest idle gaps, each named by what the host was doing then: the
  innermost harness annotation, and the host event or JAX compile event
  that overlaps the gap most.

Busy time and program times are averaged over the chips of the run.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"

Interval = Tuple[float, float]


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float                         # mean over chips
    chips: int
    programs: Dict[str, float]            # program -> device s (mean over chips)
    program_calls: Dict[str, int]         # program -> executions (chip 0)
    ops: Dict[str, float]                 # operation -> device s (mean over chips)
    gaps: List[Tuple[str, float]]         # (what the host did, s), longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def device_seconds(self, names: Iterable[str]) -> float:
        return sum(self.programs.get(n, 0.0) for n in names)


def find_trace(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def program_name(module: str) -> str:
    """'jit_fused_run(123)' -> 'fused_run'; 'jit__lambda_.4' -> '_lambda_'."""
    name = re.sub(r"\(\d+\)$", "", module.strip())
    name = re.sub(r"\.\d+$", "", name)
    return name[4:] if name.startswith("jit_") else name


CONTAINERS = ("while", "cond", "conditional", "call")


def op_name(hlo: str) -> Optional[str]:
    """'%fusion.520 = f32[...] fusion(...)' -> 'fusion.520'. Control-flow
    containers (a while loop, a conditional) span the operations inside
    them, so they are left out to count no time twice."""
    name = hlo.split(" = ", 1)[0].strip().lstrip("%")
    return None if name.split(".")[0] in CONTAINERS else name


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def _clip(a: float, b: float, lo: float, hi: float) -> Optional[Interval]:
    a, b = max(a, lo), min(b, hi)
    return (a, b) if b > a else None


def _events(plane, line_name: str):
    for line in plane.lines:
        if line.name == line_name:
            for ev in line.events:
                yield ev.name, ev.start_ns, ev.start_ns + ev.duration_ns


def reduce(path: str, compile_spans: Sequence[Tuple[str, float, float]] = (),
           top: int = 10) -> Reduction:
    """``compile_spans``: (event, start, end) on the trace's clock in ns
    (the harness converts JAX's monitoring events before it calls)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    planes = list(pd.planes)
    host = [p for p in planes if p.name.startswith("/host:")]
    host_events = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                   for p in host for line in p.lines for ev in line.events]
    marks = [e for e in host_events if e[0] == WINDOW]
    if not marks:
        raise ValueError(f"no {WINDOW} annotation in {path}")
    _, w0, w1 = marks[0]
    devices = sorted((p for p in planes if DEVICE_PLANE.match(p.name)),
                     key=lambda p: p.name)
    if not devices:
        raise ValueError(f"no TPU device plane in {path}")

    busy, programs, ops, calls = [], {}, {}, {}
    gaps_chip0: List[Interval] = []
    for k, plane in enumerate(devices):
        spans = []
        for name, a, b in _events(plane, MODULES_LINE):
            iv = _clip(a, b, w0, w1)
            if iv is None:
                continue
            spans.append(iv)
            prog = program_name(name)
            programs[prog] = programs.get(prog, 0.0) + (iv[1] - iv[0]) * 1e-9
            if k == 0:
                calls[prog] = calls.get(prog, 0) + 1
        for name, a, b in _events(plane, OPS_LINE):
            iv = _clip(a, b, w0, w1)
            op = op_name(name)
            if iv is not None and op is not None:
                ops[op] = ops.get(op, 0.0) + (iv[1] - iv[0]) * 1e-9
        merged = union(spans)
        busy.append(sum(b - a for a, b in merged) * 1e-9)
        if k == 0:
            edges = [w0] + [x for iv in merged for x in iv] + [w1]
            gaps_chip0 = [(edges[i], edges[i + 1])
                          for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
    n = len(devices)
    longest = sorted(gaps_chip0, key=lambda g: g[0] - g[1])[:top]
    named = [(_gap_name(g, host_events, compile_spans), (g[1] - g[0]) * 1e-9)
             for g in longest]
    return Reduction(
        window_s=(w1 - w0) * 1e-9, busy_s=sum(busy) / n, chips=n,
        programs={k: v / n for k, v in programs.items()}, program_calls=calls,
        ops={k: v / n for k, v in ops.items()}, gaps=named)


def _overlap(a: float, b: float, iv: Interval) -> float:
    return max(0.0, min(b, iv[1]) - max(a, iv[0]))


def _gap_name(gap: Interval, host_events, compile_spans) -> str:
    a, b = gap
    inner = [e for e in host_events if e[0].startswith("bench.") and e[0] != WINDOW
             and e[1] <= b and e[2] >= a]
    where = min(inner, key=lambda e: e[2] - e[1])[0] if inner else "between calls"
    best, most = None, 0.0
    for name, s, e in list(compile_spans) + [
            ev for ev in host_events if not ev[0].startswith("bench.")]:
        ov = _overlap(s, e, gap)
        if ov > most or (ov == most and ov > 0 and best and e - s < best[1]):
            best, most = (name, e - s), ov
    return f"{where}: {best[0]}" if best else where
