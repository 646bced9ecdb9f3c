"""EBFT: block-wise fine-tuning of sparse LLMs (the paper's contribution).

Algorithm 1, faithfully:

    for block l = 1..L:
        E ← block-wise reconstruction error (Eq. 4) over D_c
        repeat up to T epochs, early-stopping when E converges:
            W̄ₗ ← W̄ₗ − α · ∇_{W̄ₗ} E          (backprop through the block)
        advance the sparse stream with the tuned block

Paper hyper-parameters: D_c = 256×1024-token C4 segments, T = 10 epochs,
α = 2e-4 (Adam). Masks are frozen throughout — only surviving weights
move; the mask is applied *inside* the loss (W̄ = M ⊙ W), so pruned slots
get exactly zero gradient by the chain rule.

Streaming property (the paper's 16 GB claim): only one block's weights +
optimizer moments are live at a time; the teacher/student streams advance
microbatch-wise. The walk realizes the DESIGN.md §3 pipelining: block
l+1's teacher stream is dispatched while block l fine-tunes
(core/pruning/common.py, ``TeacherPrefetcher``).

The per-block tuning loop itself is FUSED (``fused_epochs``, default on):
each block's microbatches are stacked along a leading axis and the whole
epoch budget runs as one jitted ``lax.scan`` over epochs (inner scan over
microbatches), with the plateau early-stop evaluated on device
(``plateau_early_stop_device``) via ``lax.cond`` — converged blocks skip
their remaining epochs without a host round-trip. Block weights are
DONATED into the fused call, so weights and Adam moments update in place
and the measured ``live_block_bytes`` stays one-block-sized. The host
syncs once per block (one ``device_get`` of scalars + the loss history)
instead of once per microbatch-step: ≤ 3 tune-path dispatches and 1 host
sync per block, vs. epochs × microbatches + 2 × microbatches before
(docs/PERF.md has the accounting). Ragged microbatch shapes fall back to
the legacy per-step loop.

Zamba2's shared attention block (one weight set, G invocation sites) is
fine-tuned once on the *sum* of its per-site reconstruction errors
(DESIGN.md §5): site data is collected during the walk and the shared
block is tuned on the union afterwards.

Mesh-aware mode (docs/DISTRIBUTED.md): when ``EBFTConfig.mesh_plan`` is
an active :class:`~repro.distributed.meshplan.MeshPlan`, the stacked
calibration microbatches are sharded over the mesh's batch axes and the
live block's weights/masks (and, by inheritance inside the donated
dispatch, its Adam moments) over ``"model"``; the fused scan then runs
SPMD — GSPMD inserts the psum gradient all-reduce across the data axes —
while the one-live-block-per-device memory property *improves* to
one-live-block-SHARD per device. Single-device behavior (``mesh_plan``
None/inactive) is bit-for-bit unchanged, and ragged shapes still fall
back to the unsharded legacy loop.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import reconstruction as R
from repro.core.pruning import common as C
from repro.obs import metrics as OM
from repro.obs import trace as OT
from repro.obs.profile import DispatchLedger, ebft_live_block_bytes, live_bytes
from repro.optim.optimizers import adam, apply_updates
from repro.optim.schedules import plateau_early_stop, plateau_early_stop_device
from repro.sparsity.sparse_params import apply_masks

Params = Any


@dataclasses.dataclass
class EBFTConfig:
    lr: float = 2e-4
    epochs: int = 10          # paper: T = 10
    microbatch: int = 8
    patience: int = 2         # early stop when loss plateaus (paper: "converged")
    rel_tol: float = 1e-3
    seed: int = 0
    fused_epochs: bool = True  # one scanned+donated dispatch per block
    prefetch_depth: int = 1    # teacher stream dispatched this many blocks ahead
    mesh_plan: Optional[Any] = None  # MeshPlan; None/inactive = single device


@dataclasses.dataclass
class BlockReport:
    index: int
    kind: str
    epochs_run: int
    loss_before: float
    loss_after: float
    early_stop: str = "max_epochs"   # "plateau" | "max_epochs"
    history: List[float] = dataclasses.field(default_factory=list)
    live_bytes: int = 0              # weights + masks + f32 Adam moments
    path: str = "fused"              # "fused" | "legacy"
    dispatches: int = 0              # tune-path device dispatches for this block
    host_syncs: int = 0              # tune-path device→host syncs for this block
    device_dispatches: int = 0       # dispatches x participating devices
    live_bytes_per_shard: int = 0    # live_bytes per device under the MeshPlan
    collective_bytes: int = 0        # analytic grad all-reduce wire bytes

    def asdict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


# ---------------------------------------------------------------------------
def _make_tune_step(model, kind_rep_i: int, ecfg: EBFTConfig):
    """Per-block-kind executables (same shapes ⇒ same executable for every
    layer of the kind): the legacy per-microbatch ``step``/``eval_loss``
    pair and the fused whole-block ``fused_run``."""
    opt = adam(ecfg.lr)

    def loss_fn(bw, mask_bp, h, target, pos, aux):
        return R.block_loss(model, kind_rep_i, bw, mask_bp, h, target, pos, aux)

    vg = jax.value_and_grad(loss_fn)

    @jax.jit
    def step(bw, opt_state, mask_bp, h, target, pos, aux):
        loss, g = vg(bw, mask_bp, h, target, pos, aux)
        upd, opt_state = opt.update(g, opt_state, bw)
        return apply_updates(bw, upd), opt_state, loss

    @jax.jit
    def eval_loss(bw, mask_bp, h, target, pos, aux):
        return loss_fn(bw, mask_bp, h, target, pos, aux)

    # -- the fused path: whole tuning loop in one donated dispatch ---------
    E, patience, rel_tol = ecfg.epochs, ecfg.patience, ecfg.rel_tol

    def fused_run(bw, mask_bp, h_st, target_st, pos_st, aux_st):
        data = (h_st, target_st, pos_st, aux_st)
        n_mb = h_st.shape[0]

        def eval_mean(bw_):
            def body(acc, mb):
                h, t, p, a = mb
                return acc + loss_fn(bw_, mask_bp, h, t, p, a), None

            tot, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), data)
            return tot / n_mb

        before = eval_mean(bw)
        opt_state = opt.init(bw)
        hist = jnp.full((E + 1,), jnp.inf, jnp.float32).at[0].set(before)

        def mb_step(carry, mb):
            bw_, opt_state_ = carry
            h, t, p, a = mb
            loss, g = vg(bw_, mask_bp, h, t, p, a)
            upd, opt_state_ = opt.update(g, opt_state_, bw_)
            return (apply_updates(bw_, upd), opt_state_), loss

        def epoch_body(carry, e):
            bw_, opt_state_, hist_, n_run, plateaued = carry

            def live(operand):
                bw_, opt_state_, hist_, n_run = operand
                (bw_, opt_state_), losses = jax.lax.scan(
                    mb_step, (bw_, opt_state_), data
                )
                mean = jnp.mean(losses)
                hist_ = hist_.at[e + 1].set(mean)
                n_run = n_run + 1
                stop = plateau_early_stop_device(
                    hist_, n_run + 1, patience, rel_tol
                )
                return bw_, opt_state_, hist_, n_run, stop

            def skip(operand):
                bw_, opt_state_, hist_, n_run = operand
                return bw_, opt_state_, hist_, n_run, jnp.asarray(True)

            out = jax.lax.cond(
                plateaued, skip, live, (bw_, opt_state_, hist_, n_run)
            )
            return out, None

        init = (bw, opt_state, hist, jnp.zeros((), jnp.int32),
                jnp.asarray(False))
        (bw, _, hist, n_run, plateaued), _ = jax.lax.scan(
            epoch_body, init, jnp.arange(E)
        )
        after = eval_mean(bw)
        bw = apply_masks(bw, mask_bp)
        return bw, before, after, hist, n_run, plateaued

    # donate bw: weights + (internal) Adam moments update in place, so the
    # live-block footprint stays at one block (the paper's 16 GB property)
    fused = jax.jit(fused_run, donate_argnums=(0,))
    return opt, step, eval_loss, fused


def _stack_microbatches(data: List[Tuple]):
    """[(h, target, pos, aux), ...] -> one stacked pytree tuple with a
    leading microbatch axis, or None when shapes are ragged (the fused
    scan needs a uniform leading axis)."""
    if not data:
        return None
    leaves0, treedef0 = jax.tree.flatten(data[0])
    sig0 = [(jnp.shape(x), jnp.result_type(x)) for x in leaves0]
    for mb in data[1:]:
        leaves, treedef = jax.tree.flatten(mb)
        if treedef != treedef0 \
                or [(jnp.shape(x), jnp.result_type(x)) for x in leaves] != sig0:
            return None
    return jax.tree.map(lambda *xs: jnp.stack(xs), *data)


def tune_block(
    model,
    i: int,
    bp: Params,
    mask_bp: Params,
    data: List[Tuple],  # [(h, target, pos, aux), ...] microbatches
    ecfg: EBFTConfig,
    step_cache: Dict,
    stacked: Optional[Tuple] = None,  # pre-stacked (h, target, pos, aux)
) -> Tuple[Params, BlockReport]:
    kind = R.block_kind(model, i)
    if kind not in step_cache:
        step_cache[kind] = _make_tune_step(model, i, ecfg)
    opt, step, eval_loss, fused = step_cache[kind]
    plan = ecfg.mesh_plan
    sharded = plan is not None and plan.active and ecfg.fused_epochs
    ledger = DispatchLedger(
        "ebft/tune", devices=plan.device_count if sharded else 1
    )

    with OT.span("ebft/block", index=i, kind=kind) as sp:
        if ecfg.fused_epochs and stacked is None:
            stacked = _stack_microbatches(data)
        if ecfg.fused_epochs and stacked is not None:
            if sharded:
                # block weights/masks over "model" (moments inherit inside
                # the donated dispatch), calibration batch over the data
                # axes; re-putting already-sharded walk streams is a no-op
                bp = plan.put_block(bp)
                mask_bp = plan.put_block(mask_bp)
                stacked = plan.put_stacked(stacked)
            bp, report = _tune_block_fused(
                i, kind, bp, mask_bp, stacked, fused, ledger
            )
            if sharded:
                # analytic wire accounting: one psum of the block's grads
                # per optimizer step (epochs x microbatches), ring cost
                n_mb = int(jax.tree.leaves(stacked)[0].shape[0])
                steps = report.epochs_run * n_mb
                report.collective_bytes = steps * plan.allreduce_bytes(
                    live_bytes(bp)
                )
        else:
            bp, report = _tune_block_legacy(
                i, kind, bp, mask_bp, data, ecfg, opt, step, eval_loss, ledger
            )
        report.device_dispatches = ledger.device_dispatches

        live = 0
        if OT.enabled():
            # the streaming claim, measured: only this block's weights,
            # masks, and Adam moments are optimizer-live right now
            live = ebft_live_block_bytes(bp, mask_bp)
            live_shard = live
            if sharded:
                moments = jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct(np.shape(x), np.float32),
                    bp,
                )
                live_shard = (plan.sharded_bytes(bp)
                              + plan.sharded_bytes(mask_bp)
                              + 2 * plan.sharded_bytes(moments))
            report.live_bytes_per_shard = live_shard
            OM.gauge("ebft/live_block_bytes").set(live)  # summary max = peak
            OM.gauge("ebft/live_block_bytes_per_shard").set(live_shard)
            if report.collective_bytes:
                OM.counter("ebft/collective_bytes").inc(report.collective_bytes)
                OM.gauge("ebft/collective_bytes_per_block").set(
                    report.collective_bytes
                )
            OM.series("ebft/loss_before").append(report.loss_before, step=i)
            OM.series("ebft/loss_after").append(report.loss_after, step=i)
            OM.series("ebft/epochs_run").append(report.epochs_run, step=i)
            OM.series("ebft/dispatches_per_block").append(
                report.dispatches, step=i
            )
            OM.series("ebft/host_syncs_per_block").append(
                report.host_syncs, step=i
            )
            OM.counter(f"ebft/early_stop/{report.early_stop}").inc()
            sp.set(epochs=report.epochs_run, loss_before=report.loss_before,
                   loss_after=report.loss_after, early_stop=report.early_stop,
                   live_bytes=live, path=report.path,
                   dispatches=report.dispatches, host_syncs=report.host_syncs,
                   devices=ledger.devices)
        report.live_bytes = live
    return bp, report


def _tune_block_fused(
    i: int, kind: str, bp: Params, mask_bp: Params, stacked: Tuple,
    fused: Callable, ledger: DispatchLedger,
) -> Tuple[Params, BlockReport]:
    """One donated dispatch for the whole block; one host sync for the
    scalars + loss history."""
    h_st, target_st, pos_st, aux_st = stacked
    bp, before_d, after_d, hist_d, n_run_d, plateaued_d = fused(
        bp, mask_bp, h_st, target_st, pos_st, aux_st
    )
    ledger.dispatch()
    before, after, hist, epochs_run, plateaued = jax.device_get(
        (before_d, after_d, hist_d, n_run_d, plateaued_d)
    )
    ledger.host_sync()
    epochs_run = int(epochs_run)
    history = [float(v) for v in hist[: epochs_run + 1]]
    early_stop = "plateau" if bool(plateaued) else "max_epochs"
    return bp, BlockReport(
        i, kind, epochs_run, float(before), float(after), early_stop,
        history, 0, "fused", ledger.dispatches, ledger.host_syncs,
    )


def _tune_block_legacy(
    i: int, kind: str, bp: Params, mask_bp: Params, data: List[Tuple],
    ecfg: EBFTConfig, opt, step, eval_loss, ledger: DispatchLedger,
) -> Tuple[Params, BlockReport]:
    """Per-microbatch dispatch loop (ragged shapes / ``fused_epochs=False``).

    Still avoids per-microbatch host syncs: per-epoch means are reduced on
    device and transferred as one scalar (the plateau check is host-side
    here, so one sync per epoch is the floor)."""

    def eval_mean(bp_) -> float:
        losses = [eval_loss(bp_, mask_bp, *mb) for mb in data]
        ledger.dispatch(len(losses) + 1)
        ledger.host_sync()
        return float(jnp.mean(jnp.stack(losses)))  # obs: sync-ok (one scalar)

    before = eval_mean(bp)
    opt_state = opt.init(bp)
    history: List[float] = [before]
    epochs_run = 0
    early_stop = "max_epochs"
    for _ in range(ecfg.epochs):
        losses = []
        for mb in data:
            bp, opt_state, loss = step(bp, opt_state, mask_bp, *mb)
            losses.append(loss)
        ledger.dispatch(len(losses) + 1)
        ledger.host_sync()
        epochs_run += 1
        # obs: sync-ok (host-side plateau check needs the epoch mean)
        history.append(float(jnp.mean(jnp.stack(losses))))
        if plateau_early_stop(history, ecfg.patience, ecfg.rel_tol):
            early_stop = "plateau"
            break
    after = eval_mean(bp)
    bp = apply_masks(bp, mask_bp)
    ledger.dispatch()
    return bp, BlockReport(
        i, kind, epochs_run, before, after, early_stop, history, 0,
        "legacy", ledger.dispatches, ledger.host_syncs,
    )


# ---------------------------------------------------------------------------
def finetune(
    model,
    dense_params: Params,
    pruned_params: Params,
    masks: Params,
    calib: np.ndarray,
    ecfg: Optional[EBFTConfig] = None,
    extra_batch: Optional[Dict[str, np.ndarray]] = None,
    log: Optional[Callable[[str], None]] = None,
) -> Tuple[Params, List[BlockReport]]:
    """The EBFT driver. Returns (fine-tuned sparse params, per-block reports)."""
    ecfg = ecfg or EBFTConfig()
    plan = ecfg.mesh_plan
    mesh_devices = plan.device_count if plan is not None and plan.active else 1
    with OT.span("ebft/walk", epochs=ecfg.epochs, lr=ecfg.lr,
                 microbatch=ecfg.microbatch, fused=ecfg.fused_epochs,
                 prefetch_depth=ecfg.prefetch_depth,
                 mesh_devices=mesh_devices):
        with OT.span("walk/setup"):
            student = apply_masks(pruned_params, masks)
        reports: List[BlockReport] = []
        step_cache: Dict = {}

        shared_idx = (
            model.num_blocks - 1 if model.cfg.family == "hybrid" else None
        )
        shared_sites: List[Tuple] = []

        def visit(i, bp, ctx):
            mask_bp = model.get_block(masks, i)
            data = list(
                zip(ctx["h_mb"], ctx["target_mb"], ctx["pos_mb"], ctx["aux_mb"])
            )
            if i == shared_idx:
                shared_sites.extend(data)  # tune once on the union (sum of sites)
                return None
            stacked = None
            if "h_st" in ctx:
                stacked = (ctx["h_st"], ctx["target_st"], ctx["pos_st"],
                           ctx["aux_st"])
            tuned, rep = tune_block(
                model, i, bp, mask_bp, data, ecfg, step_cache, stacked=stacked
            )
            reports.append(rep)
            if log:
                log(
                    f"block {i:3d} [{rep.kind}] epochs={rep.epochs_run} "
                    f"E: {rep.loss_before:.3e} -> {rep.loss_after:.3e}"
                )
            return tuned

        result = C.walk_blocks(
            model,
            dense_params,
            calib,
            visit,
            microbatch=ecfg.microbatch,
            extra_batch=extra_batch,
            params_student=student,
            dual_stream=True,
            prefetch_depth=ecfg.prefetch_depth,
            mesh_plan=ecfg.mesh_plan,
        )

        if shared_idx is not None and shared_sites:
            with OT.span("walk/tune", block=shared_idx):
                # the shared block is stored un-stacked (model.get_block
                # returns the leaves by reference, not a slice) — copy
                # before the donated fused call so `result`'s own buffers
                # are never invalidated
                bp = jax.tree.map(jnp.copy, model.get_block(result, shared_idx))
                mask_bp = model.get_block(masks, shared_idx)
                tuned, rep = tune_block(
                    model, shared_idx, bp, mask_bp, shared_sites, ecfg,
                    step_cache
                )
                reports.append(rep)
                if log:
                    log(
                        f"shared block [{rep.kind}] ({len(shared_sites)} "
                        f"site-batches) E: {rep.loss_before:.3e} -> "
                        f"{rep.loss_after:.3e}"
                    )
                result = model.set_block(result, shared_idx, tuned)
    return result, reports
