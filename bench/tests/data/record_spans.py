#!/usr/bin/env python3
"""Records the small TPU trace that ``test_bench_spans.py`` reduces.

    python3 bench/tests/data/record_spans.py <out_dir>

Under a ``bench.window`` annotation, with known host sleeps around one
jitted ``fixture_step`` (a 2048^2 matmul and tanh, about 0.1 ms on a v5e):

    sleep 10 ms                                        outside program spans
    ebft/walk
      walk/setup           sleep 20 ms
      walk/tune block=0    fixture_step, waited on; sleep 20 ms
      walk/student block=0 a fresh jitted program, built and run
    serve/step             fixture_step dispatched; sleep 10 ms
      serve/sync           its result waited on; sleep 20 ms
    sleep 10 ms                                        outside program spans

The spans are the program's own (``repro.obs.trace``, no run live: bare
profiler annotations). JAX's build events are captured as the harness
captures them (``harness/monitor.py``). Writes ``<out_dir>/spans.xplane.pb``
and ``<out_dir>/spans.events.json`` (the build events and the
``time.time()`` at which the window opened) and prints what the trace
holds.
"""
import json
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp

BENCH = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

from harness import monitor, spans, xplane  # noqa: E402
from repro.obs import trace as OT  # noqa: E402


def fixture_step(x):
    return jnp.tanh(x @ x)


def main(out_dir: str) -> None:
    if jax.default_backend() != "tpu":
        raise SystemExit("record the fixture on a TPU")
    step = jax.jit(fixture_step)
    x = jnp.ones((2048, 2048), jnp.float32)
    step(x).block_until_ready()
    mon = monitor.Monitor()
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    wall0 = time.time()
    with jax.profiler.TraceAnnotation("bench.window"):
        time.sleep(0.01)
        with OT.span("ebft/walk"):
            with OT.span("walk/setup"):
                time.sleep(0.02)
            with OT.span("walk/tune", block=0):
                step(x).block_until_ready()
                time.sleep(0.02)
            with OT.span("walk/student", block=0):
                jax.jit(lambda v: v * 2.0 + 1.0)(x).block_until_ready()
        with OT.span("serve/step"):
            y = step(x)
            time.sleep(0.01)
            with OT.span("serve/sync"):
                y.block_until_ready()
                time.sleep(0.02)
        time.sleep(0.01)
    jax.profiler.stop_trace()
    mon.close()

    os.makedirs(out_dir, exist_ok=True)
    dst = os.path.join(out_dir, "spans.xplane.pb")
    shutil.copy(xplane.find_trace(tmp), dst)
    with open(os.path.join(out_dir, "spans.events.json"), "w") as f:
        json.dump({"wall0": wall0, "events": mon.events}, f, indent=1)
    sp = spans.reduce(dst, mon.events, wall0)
    print("offset_ns", sp.offset_ns, "window_ns", sp.window[1] - sp.window[0])
    print("busy", [(a - sp.window[0], b - a) for a, b in sp.busy])
    for s in sp.spans:
        print("span", s.name, s.attrs, s.start - sp.window[0], s.end - s.start,
              "parent", s.parent, "idle", sp.idle_ns(s))
    for e in sp.builds:
        print("build", e[0], e[1] - sp.window[0], e[2] - e[1])
    print("size", os.path.getsize(dst))


if __name__ == "__main__":
    main(sys.argv[1])
