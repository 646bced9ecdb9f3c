"""One run of one cell:

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (process start to the window: weights made on the device from the
seed, masks, one warm-up of the cell's own shapes), the measured window,
then the comparison with the plain reference that decides ``correct``.
With ``--trace 0`` the result carries the cell's end-to-end metrics;
with ``--trace 1`` the window runs under the profiler and the result
carries the per-layer metrics read from the trace.

The last line of standard output is one JSON object; the numbers
compared, each beside its limit, are the last lines of standard error
and the last key of that object. Without a TPU the run stops with a
non-zero exit and prints no result.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time
from typing import Any, Dict, List, Optional

from harness import spec as S

TRACE_DIR = ".bench_trace"  # under the checkout; removed after reduction


def parse(argv: Optional[List[str]]) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def use_compile_cache(root: str) -> str:
    """``JAX_COMPILATION_CACHE_DIR`` when set, else <checkout>/.jax_cache;
    every program is cached, however small or quick to compile, so that
    only a cell's first run in a checkout compiles."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class Context:
    """What a driver gets: the cell, the seed, the program's config, the
    reference, the compile-event monitor and the devices."""

    def __init__(self, cell: S.Cell, seed: int, devices, monitor, override=None,
                 precision=None):
        from harness import program

        self.cell = cell
        # matmul precision the traffic states for the program (None: JAX's
        # default); a precision control passes the step below it
        self.precision = precision or cell.traffic.get("matmul_precision")
        self.seed = seed
        self.devices = devices
        self.monitor = monitor
        self.conf = cell.config
        self.traffic = cell.traffic
        self.override = dict(override or {})
        self.cfg = program.model_config(cell.config, **self.override)
        self.model = program.build(self.cfg)
        self.ref = cell.reference()

    def computing(self):
        """The context the program runs in: the stated matmul precision."""
        import contextlib

        import jax

        if self.precision is None:
            return contextlib.nullcontext()
        return jax.default_matmul_precision(self.precision)

    def annotate(self, name: str):
        import jax

        return jax.profiler.TraceAnnotation(f"bench.{name}")


def compared(cell: S.Cell, numbers: Dict[str, float]) -> List[Dict[str, Any]]:
    """The numbers the cell's limits file names, each with its limit; a
    number the driver reads and the file does not name is not compared."""
    missing = set(cell.limits) - set(numbers)
    if missing:
        raise S.SpecError(f"{cell.name}: the check reads no {sorted(missing)}")
    return [{"name": k, "value": float(numbers[k]), "limit": v}
            for k, v in cell.limits.items()]


def check_lines(checks: List[Dict[str, Any]]) -> List[str]:
    return [f"check {c['name']}: {c['value']:.6g} limit {c['limit']:.6g} "
            f"{'ok' if c['value'] <= c['limit'] else 'FAILED'}" for c in checks]


def run_cell(cell: S.Cell, seed: int, seconds: float, trace: bool, t0: float,
             devices, plant=None) -> Dict[str, Any]:
    """Set-up, window, check; returns the result object (not yet printed).
    ``plant(ctx)``, a test's fault (``harness/faults.py``), is in force
    from set-up to the end of the window."""
    import contextlib

    import jax

    from harness import device, monitor as M

    mon = M.Monitor()
    ctx = Context(cell, seed, devices, mon)
    trace_dir = os.path.join(cell.root, TRACE_DIR, f"{cell.name}-{seed}")
    with ctx.computing(), plant(ctx) if plant else contextlib.nullcontext():
        drv = cell.driver().Driver(ctx)
        drv.setup()
        setup_s = time.perf_counter() - t0
        setup_compiles = mon.compiles(0.0, time.time())
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        wall0 = time.time()
        with ctx.annotate("window"):
            win = drv.window(seconds)
        wall1 = time.time()
        if trace:
            jax.profiler.stop_trace()
        window_compiles = mon.compiles(wall0, wall1)
        peak = device.memory_peak_bytes(devices)
        drv.free()
    checks = compared(cell, drv.check())
    mon.close()

    dev = device.describe(devices)
    dev["memory_peak_bytes"] = peak
    metrics: Dict[str, Dict[str, Any]] = {}
    result: Dict[str, Any] = {
        "correct": all(c["value"] <= c["limit"] for c in checks),
        "attempted": int(win["attempted"]), "failed": int(win["failed"]),
        "metrics": metrics, "device": dev,
        "setup_compiles": setup_compiles, "window_compiles": window_compiles,
    }
    if not trace:
        values = {"setup_s": setup_s, **win["metrics"]}
        for m in cell.end_to_end:
            if m["name"] not in values:
                raise S.SpecError(f"the {cell.traffic['driver']} driver measures "
                                  f"no {m['name']}")
            metrics[m["name"]] = {"value": float(values[m["name"]]), "unit": m["unit"]}
    else:
        from harness import xplane

        red = xplane.reduce(xplane.find_trace(trace_dir),
                            compile_spans=_on_trace_clock(mon, trace_dir, wall0))
        view = RunView(ctx, win, red, mon, wall0, wall1)
        for m in cell.per_layer:
            value = cell.metric_reader(m["name"]).read(view)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        dev["busy_s"] = red.busy_s
        dev["window_s"] = red.window_s
        result["breakdown"] = {
            "device_ops": sorted(red.ops.items(), key=lambda kv: -kv[1])[:10]
            or sorted(red.programs.items(), key=lambda kv: -kv[1])[:10],
            "idle_gaps": [[n, s] for n, s in red.gaps[:10]],
        }
        result["device_programs"] = sorted(red.programs.items(), key=lambda kv: -kv[1])[:10]
        shutil.rmtree(trace_dir, ignore_errors=True)
    result["check"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                       for c in checks}
    return result


def _on_trace_clock(mon, trace_dir: str, wall0: float):
    """JAX's compile events on the trace's clock: the window annotation
    starts at ``wall0`` on the host clock."""
    from jax.profiler import ProfileData

    from harness import xplane

    pd = ProfileData.from_file(xplane.find_trace(trace_dir))
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == xplane.WINDOW:
                        base = ev.start_ns
                        return [(name, base + (a - wall0) * 1e9, base + (b - wall0) * 1e9)
                                for name, a, b in mon.events]
    return []


class RunView:
    """What a per-layer metric reader sees of a traced run."""

    def __init__(self, ctx: Context, win: Dict[str, Any], red, mon, wall0, wall1):
        from harness import device, flops

        self.conf = ctx.conf
        self.ref = ctx.ref
        self.traffic = ctx.traffic
        self.counts = win["counts"]
        self.trace = red
        self.peaks = device.peaks(ctx.devices[0].device_kind)
        self.chips = len(ctx.devices)
        self.flops = flops
        self.build_s = mon.build_seconds(wall0, wall1)


def main(argv: Optional[List[str]] = None, t0: Optional[float] = None,
         root: str = S.ROOT) -> int:
    t0 = time.perf_counter() if t0 is None else t0
    args = parse(argv)
    try:
        cell = S.cell(S.load_spec(root), args.workload, root)
    except S.SpecError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    src = os.path.join(root, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"bench: the program is not in this checkout ({src})", file=sys.stderr)
        return 2
    sys.path.insert(0, src)

    from harness import device

    try:
        devices = device.look(cell.chips)
    except device.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    use_compile_cache(root)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), t0, devices)
    for line in check_lines([{"name": k, **v} for k, v in result["check"].items()]):
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
