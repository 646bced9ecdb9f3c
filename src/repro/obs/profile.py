"""Profiling hooks: compile-vs-execute timing, kernel FLOPs/bytes
accounting, and pytree memory accounting.

Three tools (docs/OBSERVABILITY.md §Profiling):

  * :class:`ProfiledFn` wraps a jitted step. The first call for each
    argument signature is split AOT-style (``fn.lower`` timed, then
    ``.compile()`` timed) so compile time is attributed separately from
    execution; every execution is fenced with ``block_until_ready`` and
    recorded as a histogram. When observability is off the wrapper is a
    single branch around the raw function.

  * :func:`record_kernel` times one kernel invocation and books its
    analytic FLOPs/bytes against the roofline hardware model
    (:data:`repro.launch.rooflines.PEAKS` of the running device),
    reporting the ideal time alongside the measured one. Callers must
    skip it while tracing — timing a tracer is meaningless and fencing
    one is an error — via :func:`is_abstract`.

  * :func:`live_bytes` / :func:`param_count` / :func:`ebft_live_block_bytes`
    account pytree memory; the EBFT walk uses them to record the
    paper's streaming claim (peak live block = weights + masks + two
    f32 Adam moments) as a measurable gauge.

Program-build time of the walk and of serving is not timed here: a live
run books JAX's own build events onto the open span
(``trace.Tracer.book_build``).
"""
from __future__ import annotations

import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import numpy as np

from repro.launch.rooflines import PEAKS
from repro.obs import metrics as M
from repro.obs import trace as T


def param_count(tree: Any) -> int:
    """Total element count of a pytree (arrays or ShapeDtypeStructs)."""
    return int(sum(int(np.prod(np.shape(x))) for x in jax.tree.leaves(tree)))


def live_bytes(tree: Any) -> int:
    """Total bytes of a pytree's leaves at their stated dtypes."""
    tot = 0
    for x in jax.tree.leaves(tree):
        n = int(np.prod(np.shape(x)))
        tot += n * np.dtype(getattr(x, "dtype", np.float32)).itemsize
    return tot


def ebft_live_block_bytes(block_params: Any, mask_params: Any,
                          n_moments: int = 2) -> int:
    """Live bytes while one block fine-tunes: weights + masks + f32 Adam
    moments — the quantity the paper's 16 GB claim bounds."""
    return (live_bytes(block_params) + live_bytes(mask_params)
            + n_moments * param_count(block_params) * 4)


def is_abstract(*values: Any) -> bool:
    """True when any leaf is a jax tracer (we are inside a jit trace)."""
    for v in values:
        for leaf in jax.tree.leaves(v):
            if isinstance(leaf, jax.core.Tracer):
                return True
    return False


# ---------------------------------------------------------------------------
class DispatchLedger:
    """Counts device dispatches and host-sync events for one region.

    The fused-EBFT acceptance budget (docs/PERF.md) is expressed in these
    two numbers: a *dispatch* is one jitted-executable launch enqueued on
    the device stream; a *host sync* is one device→host transfer the host
    blocks on (``float(x)``, ``np.asarray(x)``, ``device_get``,
    ``block_until_ready``). The ledger is a plain counter pair — always
    live, so :class:`~repro.core.ebft.BlockReport` carries real numbers
    even with observability off — and mirrors into the metrics registry
    when one is installed.

    ``devices`` (a mesh-aware walk passes its device count) additionally
    books every SPMD launch per participating device under
    ``<name>/device_dispatches`` — one host-side dispatch of an SPMD
    executable enqueues work on all ``devices`` chips, and the per-device
    ledger in ``BENCH_ebft.json`` is derived from this counter.
    """

    __slots__ = ("name", "dispatches", "host_syncs", "devices")

    def __init__(self, name: str, devices: int = 1):
        self.name = name
        self.dispatches = 0
        self.host_syncs = 0
        self.devices = max(int(devices), 1)

    @property
    def device_dispatches(self) -> int:
        return self.dispatches * self.devices

    def dispatch(self, n: int = 1) -> None:
        self.dispatches += n
        M.counter(f"{self.name}/dispatches").inc(n)
        M.counter(f"{self.name}/device_dispatches").inc(n * self.devices)

    def host_sync(self, n: int = 1) -> None:
        self.host_syncs += n
        M.counter(f"{self.name}/host_syncs").inc(n)


# ---------------------------------------------------------------------------
def record_kernel(name: str, flops: float, bytes_moved: float,
                  fn: Callable, *args, attrs: Optional[Dict[str, Any]] = None,
                  **kw):
    """Run ``fn(*args, **kw)`` fenced and book it against the roofline.

    Callers guard with ``trace.enabled() and not is_abstract(...)`` so
    the disabled/traced path never reaches here. ``attrs`` (the chosen
    tile plan from repro.kernels.tuning, when one was resolved) opens a
    kernel span carrying them, so traces show which plan each launch ran.
    """
    t0 = time.perf_counter()
    if attrs:
        with T.span(name, **attrs) as sp:
            out = sp.fence(fn(*args, **kw))
    else:
        out = fn(*args, **kw)
        jax.block_until_ready(out)
    dt = time.perf_counter() - t0
    M.histogram(f"{name}/exec_s").observe(dt)
    M.counter(f"{name}/calls").inc()
    M.counter(f"{name}/flops").inc(flops)
    M.counter(f"{name}/bytes").inc(bytes_moved)
    # ideal time on this chip: the larger of the compute and memory terms
    # (same two-term model as launch/rooflines.terms); a device the peaks
    # table does not know gets no figure rather than an assumed one
    peak = PEAKS.get(jax.devices()[0].device_kind)
    if peak is not None:
        M.gauge(f"{name}/roofline_ideal_s").set(
            max(flops / peak["flops"], bytes_moved / peak["hbm_bw"])
        )
    return out


# ---------------------------------------------------------------------------
class ProfiledFn:
    """Wraps a jitted callable; separates compile time from execution.

    Per argument signature (treedef + leaf shapes/dtypes) the wrapper
    lowers and compiles once, timing each stage; subsequent calls hit
    the cached executable and only record fenced execution time. The
    callee must be jitted; a failed lowering or compile raises.
    """

    def __init__(self, fn: Callable, name: str):
        self.fn = fn
        self.name = name
        self._compiled: Dict[Any, Callable] = {}

    def _sig(self, args: Tuple) -> Any:
        leaves, treedef = jax.tree.flatten(args)
        return treedef, tuple(
            (np.shape(x), str(getattr(x, "dtype", type(x).__name__)))
            for x in leaves
        )

    def __call__(self, *args):
        if not T.enabled():
            return self.fn(*args)
        if is_abstract(*args):  # never profile inside an outer trace
            return self.fn(*args)

        sig = self._sig(args)
        target = self._compiled.get(sig)
        if target is None:
            target = self._compile(sig, args)
        t0 = time.perf_counter()
        out = target(*args)
        jax.block_until_ready(out)
        M.histogram(f"{self.name}/exec_s").observe(time.perf_counter() - t0)
        M.counter(f"{self.name}/calls").inc()
        return out

    def _compile(self, sig: Any, args: Tuple) -> Callable:
        t0 = time.perf_counter()
        lowered = self.fn.lower(*args)
        t_lower = time.perf_counter() - t0
        t0 = time.perf_counter()
        target = lowered.compile()
        t_compile = time.perf_counter() - t0
        M.gauge(f"{self.name}/lower_s").set(t_lower)
        M.gauge(f"{self.name}/compile_s").set(t_compile)
        M.counter(f"{self.name}/compiles").inc()
        self._compiled[sig] = target
        return target


def profiled(fn: Callable, name: str) -> ProfiledFn:
    """Wrap ``fn`` (ideally ``jax.jit``-ed) with compile/exec profiling."""
    return ProfiledFn(fn, name)
