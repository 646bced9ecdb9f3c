#!/usr/bin/env python3
"""Records the small TPU trace that ``test_bench_xplane.py`` reduces.

    python3 bench/tests/data/record_trace.py <out_dir>

Under a ``bench.window`` annotation: three calls of a jitted
``fixture_step`` (a 2048^2 matmul and tanh), each in a ``bench.call``
annotation and followed by a 20 ms host sleep, then one call of a jitted
``lambda``. Writes ``<out_dir>/fixture.xplane.pb`` and prints each
program execution the device plane holds.
"""
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp


def fixture_step(x):
    return jnp.tanh(x @ x)


def main(out_dir: str) -> None:
    if jax.default_backend() != "tpu":
        raise SystemExit("record the fixture on a TPU")
    step = jax.jit(fixture_step)
    other = jax.jit(lambda x: x @ x + 1.0)
    x = jnp.ones((2048, 2048), jnp.float32)
    jax.block_until_ready((step(x), other(x)))
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.call"):
                step(x).block_until_ready()
            time.sleep(0.02)
        other(x).block_until_ready()
    jax.profiler.stop_trace()
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    from harness import xplane

    os.makedirs(out_dir, exist_ok=True)
    dst = os.path.join(out_dir, "fixture.xplane.pb")
    shutil.copy(xplane.find_trace(tmp), dst)
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(dst).planes:
        print("plane", plane.name, [(ln.name, sum(1 for _ in ln.events)) for ln in plane.lines])
        if xplane.DEVICE_PLANE.match(plane.name):
            for ln in plane.lines:
                if ln.name == xplane.MODULES_LINE:
                    for ev in ln.events:
                        print("  module", ev.name, ev.start_ns, ev.duration_ns)
    print("size", os.path.getsize(dst))


if __name__ == "__main__":
    main(sys.argv[1])
