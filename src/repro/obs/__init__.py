"""repro.obs — tracing, metrics, and profiling for the EBFT pipeline.

The paper's headline claims are operational (one live block, ~30 min
walks, 16 GB peak), so the pipeline needs to be *observable*: this
package provides the three primitives every driver/benchmark uses
instead of ``print()`` + ``time.time()`` (DESIGN.md §8,
docs/OBSERVABILITY.md):

  * :mod:`repro.obs.trace`   — nested host-time spans that are also
    ``jax.profiler`` annotations, so under a profiler session each span
    sits on the device trace's clock beside the programs it launched::

        from repro.obs import trace
        with trace.span("walk/tune", block=i):
            out = step(...)             # host time; device time: the trace

  * :mod:`repro.obs.metrics` — counters / gauges / histograms /
    time-series with a JSON summary and JSONL event stream::

        from repro.obs import metrics
        metrics.counter("serve/tokens").inc(n)
        metrics.gauge("ebft/live_block_bytes").set(b)   # tracks max = peak

  * :mod:`repro.obs.profile` — compile-vs-execute timing for jitted
    steps, analytic FLOPs/bytes accounting for the Pallas kernels
    (roofline model from :mod:`repro.launch.rooflines`), and pytree
    byte/param accounting for the paper's live-block-memory claim.

Everything is **off by default**: the module-level registry is a null
singleton whose methods allocate nothing, and the null tracer's spans
are bare profiler annotations (about a microsecond each), until
:func:`repro.obs.run.start_run` swaps in live objects. Instrumentation is host-side only — spans and metric updates
must never be traced into jitted code (kernel hooks skip themselves
when they see abstract tracers).

``python -m repro.obs report <artifact>`` renders a run's trace tree
and metric summaries; ``... validate`` checks the manifest schema (the
CI gate for ``BENCH_ebft.json``).
"""
from __future__ import annotations

from repro.obs import metrics, profile, trace  # noqa: F401  (public facades)
from repro.obs.run import Run, current_run, start_run  # noqa: F401


def enabled() -> bool:
    """True when a live run is collecting (the null tracer reports False)."""
    return trace.enabled()
