"""Shape-keyed tile autotuner with a persistent plan cache (docs/PERF.md).

The three Pallas kernels default to MXU-aligned 128 tiles regardless of
problem shape. This module closes the gap the Triton-style stacks close
with ``@autotune``: for each *launch shape* it enumerates the valid tile
plans (through the same :mod:`repro.kernels.validation` builders the
kernels execute — a candidate that builds is a candidate that launches),
measures them (median of k fenced runs; interpret-mode Pallas on CPU so
CI exercises the identical path), and persists the winner in an on-disk
JSON cache so later processes start at the best plan with zero search
time.

Cache entries are keyed by ``(kernel, dims, dtypes, params, backend,
device_kind, code_rev)`` — ``code_rev`` is a hash of this package's
sources, so editing a kernel invalidates its entries by construction
(they simply stop matching; ``repro.analysis`` pass ``tuning_cache``
flags the stale leftovers). Writes are atomic (tmp + ``os.replace``).

Three modes, threaded through ``RunSpec --kernel-tune`` and the env::

    off     never consult the cache; kernels run their 128 defaults
    cache   use a cached plan when present, defaults on a miss (default
            for the launchers; free — one dict lookup per call)
    search  on a miss, run the measured search and persist the winner

Env overrides: ``REPRO_KERNEL_TUNE`` (mode), ``REPRO_KERNEL_CACHE``
(cache path). The module default is ``off`` so library users and the
test suite see bit-identical default-tile behavior unless they opt in.

Observability: resolution outcomes count into ``kernels/tuning/{hits,
misses,searches}`` and search wall time into ``kernels/tuning/search_s``
(null-registry no-ops when no run is live); :func:`stats` carries the
same numbers host-side for ``BENCH_ebft.json``'s ``kernel_tuning``
section regardless of obs state.
"""
from __future__ import annotations

import hashlib
import itertools
import json
import os
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.kernels.validation import (
    VMEM_BUDGET_BYTES,
    KernelPlan,
    plan_flash_attention,
    plan_masked_matmul,
    plan_nm_spmm,
)

SCHEMA = "repro.kernels.tuning/v1"
MODES = ("off", "cache", "search")
DEFAULT_CACHE_PATH = os.path.join("experiments", "kernel_cache.json")

# candidate tile sizes per axis, largest first (MXU/VPU want powers of
# two; the plan builders clamp to the problem dim and reject non-divisors)
TILE_OPTIONS = (256, 128, 64, 32)
# interpret-mode Pallas executes the grid step-by-step on the host; cap
# the grid so a CPU search never times a pathological 10k-step launch
INTERPRET_GRID_CAP = 256


# ---------------------------------------------------------------------------
# module state: mode, cache path, loaded cache, resolution stats
# ---------------------------------------------------------------------------
class _State:
    __slots__ = ("mode", "path", "cache", "loaded", "stats")

    def __init__(self) -> None:
        self.mode = os.environ.get("REPRO_KERNEL_TUNE", "off")
        self.path = os.environ.get("REPRO_KERNEL_CACHE", DEFAULT_CACHE_PATH)
        self.cache: Dict[str, Dict[str, Any]] = {}
        self.loaded = False
        self.stats = _zero_stats()


def _zero_stats() -> Dict[str, float]:
    return {"hits": 0, "misses": 0, "searches": 0, "search_s": 0.0}


_STATE = _State()


def configure(mode: Optional[str] = None, path: Optional[str] = None) -> None:
    """Set the resolution mode and/or cache path (None = keep current).

    Changing the path drops the in-memory cache so the next resolve
    reloads from disk.
    """
    if mode is not None:
        if mode not in MODES:
            raise ValueError(
                f"kernel-tune mode {mode!r} not one of {'/'.join(MODES)}"
            )
        _STATE.mode = mode
    if path is not None and path != _STATE.path:
        _STATE.path = path
        _STATE.cache = {}
        _STATE.loaded = False


def state() -> Dict[str, Any]:
    """Current knobs: mode, cache path, in-memory entry count."""
    return {"mode": _STATE.mode, "path": _STATE.path,
            "entries": len(_STATE.cache)}


def stats() -> Dict[str, float]:
    """Resolution counters since the last :func:`reset_stats`."""
    return dict(_STATE.stats)


def reset_stats() -> None:
    _STATE.stats = _zero_stats()


def _reset_for_tests(mode: str = "off") -> None:
    """Test hook: fresh state, no env influence."""
    _STATE.mode = mode
    _STATE.path = DEFAULT_CACHE_PATH
    _STATE.cache = {}
    _STATE.loaded = False
    _STATE.stats = _zero_stats()


# ---------------------------------------------------------------------------
# cache key / persistence
# ---------------------------------------------------------------------------
_CODE_REV: Optional[str] = None


def code_rev() -> str:
    """Hash of every source file in this package: the cache's staleness
    fence. An edited kernel (or tuner) makes old entries miss naturally;
    the ``tuning_cache`` analysis pass flags them for cleanup."""
    global _CODE_REV
    if _CODE_REV is None:
        h = hashlib.sha1()
        root = os.path.dirname(os.path.abspath(__file__))
        for dirpath, _dirs, files in sorted(os.walk(root)):
            for fn in sorted(files):
                if fn.endswith(".py"):
                    with open(os.path.join(dirpath, fn), "rb") as f:
                        h.update(fn.encode())
                        h.update(f.read())
        _CODE_REV = h.hexdigest()[:12]
    return _CODE_REV


def _backend_tag(interpret: bool) -> str:
    import jax

    tag = jax.default_backend()
    return f"{tag}+interpret" if interpret and tag != "cpu" else tag


def _device_kind() -> str:
    import jax

    return jax.devices()[0].device_kind


def _fmt(d: Dict[str, Any]) -> str:
    return ",".join(f"{k}={d[k]}" for k in sorted(d))


def cache_key(kernel: str, dims: Dict[str, int], dtypes: Dict[str, str],
              params: Dict[str, Any], backend: str, device_kind: str,
              rev: str) -> str:
    return "|".join([kernel, _fmt(dims), _fmt(dtypes), _fmt(params),
                     backend, device_kind, rev])


def _load() -> None:
    if _STATE.loaded:
        return
    _STATE.loaded = True
    try:
        with open(_STATE.path) as f:
            payload = json.load(f)
    except (OSError, json.JSONDecodeError):
        return
    if not isinstance(payload, dict) or payload.get("schema") != SCHEMA:
        return  # unknown schema version: start fresh, never crash a run
    entries = payload.get("entries")
    if isinstance(entries, dict):
        _STATE.cache = entries


def _save() -> None:
    """Atomic rewrite: the cache is either the old file or the new one,
    never a torn write (parallel CI jobs share the path)."""
    path = _STATE.path
    parent = os.path.dirname(path) or "."
    os.makedirs(parent, exist_ok=True)
    payload = {"schema": SCHEMA, "code_rev": code_rev(),
               "entries": _STATE.cache}
    fd, tmp = tempfile.mkstemp(dir=parent, suffix=".tmp")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(payload, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
    except OSError:
        try:
            os.unlink(tmp)
        except OSError:
            pass


# ---------------------------------------------------------------------------
# candidate generation (through the validated plan builders)
# ---------------------------------------------------------------------------
_PLANNERS: Dict[str, Tuple[Tuple[str, ...], Callable[..., KernelPlan]]] = {
    "masked_matmul": (
        ("bm", "bk", "bn"),
        lambda dims, dtypes, params, tiles: plan_masked_matmul(
            dims["M"], dims["K"], dims["N"], **tiles,
            x_dtype=dtypes.get("x", "float32"),
            w_dtype=dtypes.get("w", "float32"),
        ),
    ),
    "nm_spmm": (
        ("bm", "bk", "bn"),
        lambda dims, dtypes, params, tiles: plan_nm_spmm(
            dims["M"], dims["K"], dims["N"],
            n=params["n"], m=params["m"], **tiles,
            x_dtype=dtypes.get("x", "float32"),
            v_dtype=dtypes.get("v", "float32"),
        ),
    ),
    "flash_attention": (
        ("bq", "bk"),
        lambda dims, dtypes, params, tiles: plan_flash_attention(
            dims["BH"], dims["Sq"], dims["Sk"], dims["d"], **tiles,
            q_dtype=dtypes.get("q", "float32"),
        ),
    ),
}


def build_plan(kernel: str, dims: Dict[str, int], dtypes: Dict[str, str],
               params: Dict[str, Any], tiles: Dict[str, int]) -> KernelPlan:
    """The KernelPlan a launch with these tiles would execute (raises
    ``ValueError`` exactly where the kernel itself would)."""
    if kernel not in _PLANNERS:
        raise ValueError(f"unknown kernel {kernel!r}; "
                         f"tunable: {', '.join(_PLANNERS)}")
    names, builder = _PLANNERS[kernel]
    bad = set(tiles) - set(names)
    if bad:
        raise ValueError(f"{kernel}: unknown tile knobs {sorted(bad)}")
    return builder(dims, dtypes, params, tiles)


def candidate_tiles(
    kernel: str,
    dims: Dict[str, int],
    dtypes: Dict[str, str],
    params: Optional[Dict[str, Any]] = None,
    *,
    interpret: bool = False,
    max_candidates: int = 8,
) -> List[Dict[str, int]]:
    """Valid, deduplicated tile plans for this launch, default plan first.

    Every candidate passes the full :class:`KernelPlan` validation (grid
    divisibility after clamping, N:M group alignment) plus the VMEM
    double-buffering budget; interpret-mode candidates additionally
    respect :data:`INTERPRET_GRID_CAP`. Distinct requests that clamp to
    the same effective tiles collapse to one candidate.
    """
    params = params or {}
    names, _ = _PLANNERS[kernel] if kernel in _PLANNERS else ((), None)
    out: List[Dict[str, int]] = []
    seen: set = set()

    def admit(tiles: Dict[str, int]) -> None:
        try:
            plan = build_plan(kernel, dims, dtypes, params, tiles)
        except ValueError:
            return
        eff = tuple(sorted(plan.tiles.items()))
        if eff in seen:
            return
        if plan.vmem_bytes() > VMEM_BUDGET_BYTES:
            return
        if interpret and int(np.prod(plan.grid)) > INTERPRET_GRID_CAP:
            return
        seen.add(eff)
        out.append(dict(plan.tiles))

    admit({})  # the 128-defaults plan is always candidate 0
    for combo in itertools.product(TILE_OPTIONS, repeat=len(names)):
        if len(out) >= max_candidates:
            break
        admit(dict(zip(names, combo)))
    return out


# ---------------------------------------------------------------------------
# measured search
# ---------------------------------------------------------------------------
def _make_runner(kernel: str, dims: Dict[str, int], dtypes: Dict[str, str],
                 params: Dict[str, Any], interpret: bool) -> Callable:
    """A ``tiles -> output`` closure over synthesized operands.

    The search owns its operands (seeded numpy, shaped from ``dims``), so
    it can run from anywhere — including while an outer jit is tracing
    the real call site — and measures the kernel, not the caller's data.
    """
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    run_interpret = interpret or jax.default_backend() != "tpu"

    if kernel == "masked_matmul":
        from repro.kernels.masked_matmul.masked_matmul import masked_matmul

        x = jnp.asarray(rng.normal(size=(dims["M"], dims["K"])),
                        dtypes.get("x", "float32"))
        w = jnp.asarray(rng.normal(size=(dims["K"], dims["N"])),
                        dtypes.get("w", "float32"))
        m = jnp.asarray(rng.random((dims["K"], dims["N"])) > 0.5, jnp.int8)
        return lambda tiles: masked_matmul(
            x, w, m, interpret=run_interpret, **tiles)

    if kernel == "nm_spmm":
        from repro.kernels.nm_spmm.nm_spmm import nm_spmm

        K, N = dims["K"], dims["N"]
        n, m = params["n"], params["m"]
        G = K // m
        # one valid N:M pattern per (group, col): n distinct offsets in [0, m)
        perm = rng.permuted(
            np.broadcast_to(np.arange(m), (G, N, m)).copy(), axis=2)
        idx = np.sort(perm[:, :, :n], axis=2)          # (G, N, n)
        idx = jnp.asarray(
            idx.transpose(0, 2, 1).reshape(G * n, N), jnp.int8)
        vals = jnp.asarray(rng.normal(size=(G * n, N)),
                           dtypes.get("v", "float32"))
        x = jnp.asarray(rng.normal(size=(dims["M"], K)),
                        dtypes.get("x", "float32"))
        return lambda tiles: nm_spmm(
            x, vals, idx, n=n, m=m, interpret=run_interpret, **tiles)

    if kernel == "flash_attention":
        from repro.kernels.flash_attention.flash_attention import (
            flash_attention,
        )

        dt = dtypes.get("q", "float32")
        q = jnp.asarray(rng.normal(size=(dims["BH"], dims["Sq"], dims["d"])), dt)
        k = jnp.asarray(rng.normal(size=(dims["BH"], dims["Sk"], dims["d"])), dt)
        v = jnp.asarray(rng.normal(size=(dims["BH"], dims["Sk"], dims["d"])), dt)
        causal = bool(params.get("causal", True))
        return lambda tiles: flash_attention(
            q, k, v, causal=causal, interpret=run_interpret, **tiles)

    raise ValueError(f"unknown kernel {kernel!r}")


def _timed(run: Callable[[], Any], reps: int) -> float:
    """Median of ``reps`` fenced runs, after one untimed warm-up call
    (compile must not contaminate the comparison)."""
    import jax

    jax.block_until_ready(run())
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(run())
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def search(
    kernel: str,
    dims: Dict[str, int],
    dtypes: Dict[str, str],
    params: Optional[Dict[str, Any]] = None,
    *,
    interpret: bool = False,
    reps: int = 3,
    max_candidates: int = 8,
) -> Dict[str, Any]:
    """Measure every candidate plan; return the cache entry for the best.

    The default plan is measured *inside the same sweep*, so
    ``measured_s["best"] <= measured_s["default"]`` holds by construction
    (exact ties keep the default — ``min`` is stable) and the
    BENCH_kernels default-vs-tuned comparison is never a cross-sweep
    noise artifact.
    """
    params = params or {}
    cands = candidate_tiles(kernel, dims, dtypes, params,
                            interpret=interpret, max_candidates=max_candidates)
    if not cands:
        raise ValueError(
            f"{kernel}: no valid tile plan for dims {dims} "
            f"(params {params})"
        )
    runner = _make_runner(kernel, dims, dtypes, params, interpret)
    measured = [(_timed(lambda t=tiles: runner(t), reps), tiles)
                for tiles in cands]
    best_s, best_tiles = min(measured, key=lambda r: r[0])
    return {
        "kernel": kernel,
        "dims": dict(dims),
        "dtypes": dict(dtypes),
        "params": dict(params),
        "backend": _backend_tag(interpret),
        "device_kind": _device_kind(),
        "code_rev": code_rev(),
        "tiles": dict(best_tiles),
        "measured_s": {"default": measured[0][0], "best": best_s},
        "candidates": len(cands),
    }


def store(entry: Dict[str, Any]) -> str:
    """Insert a :func:`search` entry into the persistent cache; returns
    its key. The BENCH_kernels sweep uses this so its default-vs-tuned
    measurements double as warm cache entries for later runs."""
    _load()
    key = cache_key(entry["kernel"], entry["dims"], entry["dtypes"],
                    entry["params"], entry["backend"], entry["device_kind"],
                    entry["code_rev"])
    _STATE.cache[key] = entry
    _save()
    return key


# ---------------------------------------------------------------------------
# resolution (the wrappers' entry point)
# ---------------------------------------------------------------------------
def resolve(
    kernel: str,
    dims: Dict[str, int],
    dtypes: Dict[str, str],
    params: Optional[Dict[str, Any]] = None,
    *,
    interpret: bool = False,
) -> Tuple[Dict[str, int], Optional[str]]:
    """Tiles for this launch per the current mode.

    Returns ``(tiles, source)`` where source is ``"cache"``, ``"search"``,
    ``"default"`` (a cache-mode miss), or ``None`` (tuning off — the
    empty tile dict means the kernel runs its built-in defaults). Cached
    tiles are re-validated through the plan builder before use; a
    corrupt or stale-constraint entry degrades to a miss, never a crash.
    """
    from repro.obs import metrics as OM

    if _STATE.mode == "off":
        return {}, None
    params = params or {}
    _load()
    key = cache_key(kernel, dims, dtypes, params, _backend_tag(interpret),
                    _device_kind(), code_rev())
    entry = _STATE.cache.get(key)
    if entry is not None:
        tiles = entry.get("tiles")
        if isinstance(tiles, dict):
            try:
                tiles = {k: int(v) for k, v in tiles.items()}
                build_plan(kernel, dims, dtypes, params, tiles)
            except (ValueError, TypeError):
                entry = None  # invalid entry: fall through to a miss
        else:
            entry = None
    if entry is not None:
        _STATE.stats["hits"] += 1
        OM.counter("kernels/tuning/hits").inc()
        return tiles, "cache"

    _STATE.stats["misses"] += 1
    OM.counter("kernels/tuning/misses").inc()
    if _STATE.mode != "search":
        return {}, "default"

    t0 = time.perf_counter()
    entry = search(kernel, dims, dtypes, params, interpret=interpret)
    dt = time.perf_counter() - t0
    _STATE.stats["searches"] += 1
    _STATE.stats["search_s"] += dt
    OM.counter("kernels/tuning/searches").inc()
    OM.histogram("kernels/tuning/search_s").observe(dt)
    _STATE.cache[key] = entry
    _save()
    return dict(entry["tiles"]), "search"


# ---------------------------------------------------------------------------
# workload pre-tuning (launchers warm the cache before the hot path)
# ---------------------------------------------------------------------------
def ebft_workloads(cfg, tokens: int, seq: int,
                   pattern: Optional[Tuple[int, int]] = None) -> List[Tuple]:
    """(kernel, dims, dtypes, params) for every kernel launch an EBFT
    calibration walk over this config could make: one masked matmul per
    distinct block weight shape (M = microbatch x seq calibration
    tokens), the N:M variant when a pattern divides K, and the per-block
    flash attention at the calibration sequence length."""
    from repro.analysis.kernel_check import matmul_workloads

    f32 = "float32"
    work: List[Tuple] = []
    seen: set = set()
    for _label, M, K, N in matmul_workloads(cfg, tokens=tokens):
        if (M, K, N) in seen:
            continue
        seen.add((M, K, N))
        dims = {"M": M, "K": K, "N": N}
        work.append(("masked_matmul", dims, {"x": f32, "w": f32}, {}))
        if pattern is not None and K % pattern[1] == 0:
            work.append(("nm_spmm", dims, {"x": f32, "v": f32},
                         {"n": pattern[0], "m": pattern[1]}))
    if cfg.family != "ssm":
        mb = max(tokens // max(seq, 1), 1)
        work.append((
            "flash_attention",
            {"BH": mb * cfg.num_heads, "Sq": seq, "Sk": seq,
             "d": cfg.resolved_head_dim},
            {"q": f32}, {"causal": True},
        ))
    return work


def pretune(workloads: Sequence[Tuple], *, interpret: bool = False) -> List[Dict]:
    """Resolve each workload through the current mode (searching and
    persisting on misses when mode is ``search``); returns one record per
    workload for the launcher's log/artifact."""
    out = []
    for kernel, dims, dtypes, params in workloads:
        tiles, source = resolve(kernel, dims, dtypes, params,
                                interpret=interpret)
        out.append({"kernel": kernel, "dims": dict(dims),
                    "source": source, "tiles": tiles})
    return out
