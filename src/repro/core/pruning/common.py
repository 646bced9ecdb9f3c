"""Shared machinery for the calibration-based pruning methods.

All methods (magnitude / Wanda / SparseGPT / DSnoT / FLAP) are *layer-wise*
inside a *block-wise* walk: the dense hidden stream is propagated block by
block over the calibration set D_c, the per-linear input activations are
tapped (sparsity/taps.py), and per-leaf statistics are accumulated:

    n        total tokens seen
    sum      Σ_t X[t]              (R,)   — DSnoT's signed expected input
    sumsq    Σ_t X[t]²             (R,)   — Wanda's ‖X_j‖₂², FLAP fluctuation
    hessian  Σ_t X[t] X[t]ᵀ        (R,R)  — SparseGPT's Gram (opt-in)

Expert-batched leaves get an extra leading E axis on every stat.

The walk processes the calibration set in microbatches, so peak memory is
one block + one microbatch of activations — the same 16 GB-GPU streaming
property the paper exploits, expressed in JAX.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import reconstruction as R
from repro.sparsity import sparse_params as SP
from repro.sparsity.taps import taps_for_block

Params = Any


def tap_key(path_names: Tuple[str, ...]) -> str:
    """Map a block-param leaf path to its taps-dict key."""
    return "/".join(path_names[-2:])


def lookup_tap(taps: Dict[str, jax.Array], names: Tuple[str, ...]):
    k2 = tap_key(names)
    if k2 in taps:
        return taps[k2]
    return taps.get(names[-1])


def iter_prunable(block_params: Params):
    """Yields (path_names, leaf) for every prunable leaf of a block."""
    out = []

    def g(path, leaf):
        if SP.is_prunable(path, leaf):
            out.append((SP._path_names(path), leaf))
        return leaf

    jax.tree_util.tree_map_with_path(g, block_params)
    return out


# ---------------------------------------------------------------------------
@dataclasses.dataclass
class LeafStats:
    n: float
    sum: jax.Array      # (R,) or (E, R)
    sumsq: jax.Array    # (R,) or (E, R)
    hessian: Optional[jax.Array] = None  # (R, R) or (E, R, R)

    @property
    def mean(self):
        return self.sum / max(self.n, 1.0)

    @property
    def col_norm(self):
        return jnp.sqrt(jnp.maximum(self.sumsq, 0.0))

    @property
    def fluctuation(self):
        """Σ (X - mean)² per column (FLAP's variance mass)."""
        return jnp.maximum(self.sumsq - self.n * jnp.square(self.mean), 0.0)


def _acc_stats(x: jax.Array, want_hessian: bool) -> LeafStats:
    """x: (T, R) or (E, C, R) activation matrix for one microbatch."""
    x32 = x.astype(jnp.float32)
    if x.ndim == 3:  # expert-batched
        n = float(x.shape[1])
        s = x32.sum(axis=1)
        ss = jnp.square(x32).sum(axis=1)
        h = jnp.einsum("ecr,ecs->ers", x32, x32) if want_hessian else None
    else:
        n = float(x.shape[0])
        s = x32.sum(axis=0)
        ss = jnp.square(x32).sum(axis=0)
        h = x32.T @ x32 if want_hessian else None
    return LeafStats(n, s, ss, h)


def _merge(a: Optional[LeafStats], b: LeafStats) -> LeafStats:
    if a is None:
        return b
    h = None
    if b.hessian is not None:
        h = (a.hessian if a.hessian is not None else 0) + b.hessian
    return LeafStats(a.n + b.n, a.sum + b.sum, a.sumsq + b.sumsq, h)


# ---------------------------------------------------------------------------
def collect_block_stats(
    model,
    bp: Params,
    block_index: int,
    h_mb: List[jax.Array],
    pos_mb: List[jax.Array],
    aux_mb: List[Dict],
    want_hessian: bool = False,
) -> Dict[str, LeafStats]:
    """Run taps over each microbatch of the stream; accumulate stats."""
    cfg = model.cfg
    tapfn = taps_for_block(cfg, block_index, model.num_blocks)
    tap_jit = jax.jit(lambda bp_, h_, p_, aux_: tapfn(bp_, cfg, h_, p_, **aux_))

    stats: Dict[str, LeafStats] = {}
    for h, pos, aux in zip(h_mb, pos_mb, aux_mb):
        taps = tap_jit(bp, h, pos, aux)
        for key, x in taps.items():
            stats[key] = _merge(stats.get(key), _acc_stats(x, want_hessian))
    return stats


def stats_for_leaf(stats: Dict[str, LeafStats], names: Tuple[str, ...]) -> Optional[LeafStats]:
    k2 = tap_key(names)
    if k2 in stats:
        return stats[k2]
    return stats.get(names[-1])


# ---------------------------------------------------------------------------
# The block-by-block walk shared by the pruning drivers and EBFT.
# ---------------------------------------------------------------------------
class Unstacked:
    """Lazy per-microbatch view over a stacked pytree.

    The stacked walk keeps each stream as ONE device array with a leading
    microbatch axis; list-consuming visitors (the pruning drivers,
    mask-tuning) still read ``ctx["h_mb"][j]`` — each access slices on
    demand, so visitors that only use the stacked form (fused EBFT) incur
    zero per-microbatch dispatches.
    """

    __slots__ = ("tree", "n")

    def __init__(self, tree, n: int):
        self.tree = tree
        self.n = n

    def __len__(self) -> int:
        return self.n

    def __getitem__(self, j):
        if not -self.n <= j < self.n:
            raise IndexError(j)
        return jax.tree.map(lambda a: a[j], self.tree)

    def __iter__(self):
        return (self[j] for j in range(self.n))


def _uniform_microbatches(batch_all: List[Dict[str, jax.Array]]) -> bool:
    """True when every microbatch has identical structure + leaf shapes
    (the stacked/fused walk needs a uniform leading axis)."""
    if not batch_all:
        return False
    leaves0, treedef0 = jax.tree.flatten(batch_all[0])
    sig0 = [(x.shape, x.dtype) for x in leaves0]
    for b in batch_all[1:]:
        leaves, treedef = jax.tree.flatten(b)
        if treedef != treedef0 or [(x.shape, x.dtype) for x in leaves] != sig0:
            return False
    return True


class TeacherPrefetcher:
    """Dispatch-ahead teacher stream for the dual-stream walk (DESIGN.md §3).

    The teacher stream depends only on the frozen dense ``params``, never
    on the student's updates, so block ``l+1..l+depth``'s teacher
    activations can be *enqueued* while block ``l``'s student is still
    fine-tuning — the teacher forward overlaps student backprop on the
    device stream. ``get(k)`` fences (``block_until_ready``) at the
    consume point, which both attributes the wait to the consumer and
    back-pressures the queue: at most ``depth + 1`` blocks of teacher
    activations are in flight, keeping the walk's streaming-memory
    property intact.

    ``depth=0`` degenerates to the strictly serial legacy order (compute
    block ``l``'s targets immediately before visiting block ``l``).
    """

    def __init__(self, model, params, visits, adv_scan, ht_st, pos_st,
                 aux_t_st, depth: int, ledger: Optional[Any] = None):
        self.model = model
        self.params = params
        self.visits = visits
        self.adv_scan = adv_scan
        self.pos_st = pos_st
        self.aux_t_st = aux_t_st
        self.depth = max(int(depth), 0)
        self.ledger = ledger
        self._ht = ht_st                    # teacher stream BEFORE visit _next
        self._targets: Dict[int, Any] = {}  # visit index -> stacked targets
        self._next = 0

    def _dispatch_until(self, k: int) -> None:
        last = min(k, len(self.visits) - 1)
        while self._next <= last:
            i, _site = self.visits[self._next]
            dense_bp = self.model.get_block(self.params, i)
            t = self.adv_scan(dense_bp, self._ht, self.pos_st, self.aux_t_st, i)
            if self.ledger is not None:
                self.ledger.dispatch()
            self._targets[self._next] = t
            self._ht = t                    # Eq. 3: teacher feeds teacher
            self._next += 1

    def in_flight(self) -> int:
        return len(self._targets)

    def get(self, k: int):
        """Teacher targets for visit ``k``, fenced at the consume point."""
        self._dispatch_until(k + self.depth)
        t = self._targets.pop(k)
        jax.block_until_ready(t)
        if self.ledger is not None:
            self.ledger.host_sync()
        return t


def walk_blocks(
    model,
    params: Params,
    calib: np.ndarray,  # (N, S) token segments
    visit_fn: Callable,  # (block_index, bp, stream_ctx) -> new bp or None
    microbatch: int = 8,
    extra_batch: Optional[Dict[str, np.ndarray]] = None,
    params_student: Optional[Params] = None,
    dual_stream: bool = False,
    prefetch_depth: int = 0,
    mesh_plan: Optional[Any] = None,
):
    """Block-by-block calibration walk.

    Single-stream mode (pruning: Wanda/SparseGPT/DSnoT convention): one
    stream advances through the *already-updated* blocks; each visit sees
    that stream as input and the dense block's output on the same input as
    ``target_mb``.

    Dual-stream mode (EBFT, Eq. 3/4): the teacher stream propagates through
    the dense ``params`` and the student stream through
    ``params_student``; visits see student inputs (``h_mb``) and pure
    teacher outputs (``target_mb``). When microbatch shapes are uniform
    the streams are kept *stacked* (one device array with a leading
    microbatch axis): each stream advance is ONE scanned dispatch per
    block, the teacher stream is produced ``prefetch_depth`` blocks ahead
    of the visitor (:class:`TeacherPrefetcher`), and visitors additionally
    receive ``h_st/target_st/pos_st/aux_st`` stacked arrays so a fused
    tuner never re-stacks. Ragged shapes fall back to the per-microbatch
    list walk.

    stream_ctx fields: h_mb, pos_mb, aux_mb, target_mb, site; stacked
    mode adds h_st, target_st, pos_st, aux_st (and the ``*_mb`` views
    become lazy slices).

    ``mesh_plan`` (:class:`repro.distributed.meshplan.MeshPlan`) shards the
    stacked streams over the mesh's batch axes — teacher and student
    activations come out data-sharded, not replicated, so a fused visitor
    runs SPMD over the calibration microbatches. Inactive/None plans and
    the ragged list walk are byte-identical to the unsharded behavior.
    Returns the updated student/pruned params.
    """
    from repro.obs import trace as OT

    out_params = params_student if params_student is not None else params
    with OT.span("walk/setup"):
        batch_all = _make_batches(model.cfg, calib, extra_batch, microbatch)

    if dual_stream and _uniform_microbatches(batch_all):
        return _walk_blocks_stacked(
            model, params, out_params, batch_all, visit_fn, prefetch_depth,
            mesh_plan=mesh_plan,
        )
    return _walk_blocks_lists(
        model, params, out_params, batch_all, visit_fn, dual_stream
    )


def _walk_blocks_lists(model, params, out_params, batch_all, visit_fn,
                       dual_stream: bool):
    """Per-microbatch list walk (pruning drivers; ragged-shape fallback)."""
    adv = jax.jit(
        lambda bp, h, pos, aux, i: model.apply_block(None, i, bp, h, pos, **aux),
        static_argnames=("i",),
    )

    for seg in R.execution_plan(model):
        h0_jit = jax.jit(seg.h0)
        aux_jit = jax.jit(seg.aux)
        hs_mb, ht_mb, pos_mb, aux_s, aux_t = [], [], [], [], []
        for b in batch_all:
            h, pos = h0_jit(params, b)
            ht_mb.append(h)
            pos_mb.append(pos)
            aux_t.append(aux_jit(params, b))
            if dual_stream:
                h_s, _ = h0_jit(out_params, b)
                hs_mb.append(h_s)
                aux_s.append(aux_jit(out_params, b))
        if not dual_stream:
            hs_mb, aux_s = ht_mb, aux_t

        for (i, site) in seg.visits:
            dense_bp = model.get_block(params, i)
            # teacher/“dense on same input” targets
            target_mb = [
                adv(dense_bp, h, p, a, i)
                for h, p, a in zip(
                    (ht_mb if dual_stream else hs_mb), pos_mb,
                    (aux_t if dual_stream else aux_s),
                )
            ]
            bp = model.get_block(out_params, i)
            ctx = dict(
                h_mb=hs_mb, pos_mb=pos_mb, aux_mb=aux_s, target_mb=target_mb,
                site=site,
            )
            new_bp = visit_fn(i, bp, ctx)
            if new_bp is not None:
                out_params = model.set_block(out_params, i, new_bp)
                bp = new_bp
            # advance streams
            if dual_stream:
                ht_mb = target_mb
                hs_mb = [
                    adv(bp, h, p, a, i) for h, p, a in zip(hs_mb, pos_mb, aux_s)
                ]
            else:
                hs_mb = ht_mb = [
                    adv(bp, h, p, a, i) for h, p, a in zip(hs_mb, pos_mb, aux_s)
                ]
    return out_params


def _walk_blocks_stacked(model, params, out_params, batch_all, visit_fn,
                         prefetch_depth: int, mesh_plan=None):
    """Stacked dual-stream walk: one scanned dispatch per stream advance,
    teacher stream pipelined ``prefetch_depth`` blocks ahead. With an
    active ``mesh_plan`` the stacked streams are data-sharded at segment
    setup, so every teacher/student advance (and the prefetcher's
    in-flight targets) stays sharded — one SPMD dispatch, never a
    replicated copy per device."""
    from repro.obs import metrics as OM
    from repro.obs import trace as OT
    from repro.obs.profile import DispatchLedger

    sharded = mesh_plan is not None and mesh_plan.active
    ledger = DispatchLedger(
        "ebft/walk", devices=mesh_plan.device_count if sharded else 1
    )
    n_mb = len(batch_all)

    def adv_scan_fn(bp, h_st, pos_st, aux_st, i):
        def one(args):
            h, pos, aux = args
            return model.apply_block(None, i, bp, h, pos, **aux)

        return jax.lax.map(one, (h_st, pos_st, aux_st))

    # one program per static block index i (a per-block build)
    adv_scan = jax.jit(adv_scan_fn, static_argnames=("i",))
    # every host statement of a block sits in one phase span: walk/setup
    # (batches, stream set-up), walk/teacher (the prefetched targets'
    # fence), walk/tune (the visit and its write-back) and walk/student
    # (the student advance's dispatch). No span fences: device time
    # comes from the profiler trace
    with OT.span("walk/setup"):
        batch_st = jax.tree.map(lambda *xs: jnp.stack(xs), *batch_all)
        if sharded:
            batch_st = mesh_plan.put_stacked(batch_st)

    for seg in R.execution_plan(model):
        with OT.span("walk/setup"):
            # stream setup: one scanned dispatch per (stream, segment)
            h0_jit = jax.jit(lambda p, bst, h0=seg.h0: jax.lax.map(
                lambda b: h0(p, b), bst))
            aux_jit = jax.jit(lambda p, bst, aux=seg.aux: jax.lax.map(
                lambda b: aux(p, b), bst))
            ht_st, pos_st = h0_jit(params, batch_st)
            aux_t_st = aux_jit(params, batch_st)
            hs_st, _ = h0_jit(out_params, batch_st)
            aux_s_st = aux_jit(out_params, batch_st)
            if sharded:
                # pin the stream layout: activations batch-sharded over
                # the data axes (GSPMD usually propagates this from
                # batch_st, but the walk's memory property depends on
                # it, so make it law)
                ht_st, pos_st, aux_t_st, hs_st, aux_s_st = \
                    mesh_plan.put_stacked(
                        (ht_st, pos_st, aux_t_st, hs_st, aux_s_st))
            ledger.dispatch(4)

            pf = TeacherPrefetcher(
                model, params, seg.visits, adv_scan, ht_st, pos_st,
                aux_t_st, prefetch_depth, ledger=ledger,
            )

        for k, (i, site) in enumerate(seg.visits):
            with OT.span("walk/teacher", block=i):
                target_st = pf.get(k)
            with OT.span("walk/tune", block=i):
                bp = model.get_block(out_params, i)
                ctx = dict(
                    h_st=hs_st, target_st=target_st, pos_st=pos_st,
                    aux_st=aux_s_st, site=site,
                    h_mb=Unstacked(hs_st, n_mb),
                    target_mb=Unstacked(target_st, n_mb),
                    pos_mb=Unstacked(pos_st, n_mb),
                    aux_mb=Unstacked(aux_s_st, n_mb),
                )
                new_bp = visit_fn(i, bp, ctx)
                if new_bp is not None:
                    out_params = model.set_block(out_params, i, new_bp)
                    bp = new_bp
            with OT.span("walk/student", block=i):
                hs_st = adv_scan(bp, hs_st, pos_st, aux_s_st, i)
                ledger.dispatch()
                if OT.enabled():
                    OM.gauge("ebft/walk/prefetch_inflight").set(pf.in_flight())
    return out_params


def _make_batches(cfg, calib, extra_batch, microbatch: int) -> List[Dict[str, jax.Array]]:
    n = calib.shape[0]
    out = []
    for s in range(0, n, microbatch):
        b = {"tokens": jnp.asarray(calib[s : s + microbatch])}
        if extra_batch:
            for k, v in extra_batch.items():
                b[k] = jnp.asarray(v[s : s + microbatch])
        out.append(b)
    return out
