"""The paper's pipeline as a driver: pretrain (or load) -> prune -> EBFT
-> evaluate, with every baseline selectable.

    python -m repro.launch.ebft_run --arch tiny_dense --pretrain-steps 200 \
        --method wanda --sparsity 0.7 --lr 1e-2

Compares (per the paper's tables): no fine-tuning, DSnoT, mask-tuning,
LoRA and EBFT on held-out perplexity. On the container this runs the tiny
configs; with real devices the identical driver handles the assigned
archs (the walk is block-streamed, so memory stays one-block-sized —
the paper's 16 GB property).

``--mesh-data``/``--mesh-model`` shard the calibration walk across a
device mesh (docs/DISTRIBUTED.md); the default (1x1) is the bit-for-bit
single-device path. CPU repro of the sharded walk::

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python -m repro.launch.ebft_run --mesh-data 4 --mesh-model 2 ...

The CLI is one view of :class:`repro.launch.api.RunSpec` — the old
``--ebft-*`` flag spellings still parse through the deprecation shim.

Fully instrumented via repro.obs (docs/OBSERVABILITY.md): every phase is
a span, per-block reconstruction data flows into the metrics registry,
and the run writes a ``BENCH_ebft.json`` artifact (manifest + phases +
per-block losses + peak live-block bytes + per-device dispatch ledger +
collective bytes + perplexities) that ``python -m repro.obs report``
renders. ``--no-obs`` disables all of it; the console output is
identical either way (it is just a sink).
"""
from __future__ import annotations

import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.configs.base import ModelConfig
from repro.core import ebft, lora, mask_tuning
from repro.core.evaluate import perplexity
from repro.core.masks import prune
from repro.data.tokens import (
    CorpusConfig, SyntheticCorpus, calibration_set, corpus_iterator, eval_set,
)
from repro.kernels import tuning
from repro.launch.api import RunSpec
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_ebft_plan
from repro.models.model import build
from repro.obs import metrics as OM
from repro.obs import trace as OT
from repro.optim.optimizers import adamw
from repro.training.train_loop import make_train_step


class _phase:
    """A pipeline phase: an obs span when observability is on, and a
    plain monotonic wall-time either way (console timings survive
    ``--no-obs``)."""

    def __init__(self, name: str, **attrs):
        self.span = OT.span(name, **attrs)
        self.duration = 0.0

    def __enter__(self) -> "_phase":
        self._t0 = time.perf_counter()
        self.span.__enter__()
        return self

    def __exit__(self, *exc) -> bool:
        self.span.__exit__(*exc)
        self.duration = time.perf_counter() - self._t0
        return False

    def fence(self, value):
        return self.span.fence(value)


def _walk_phases(forest) -> Dict[str, float]:
    """``<phase>``: the walk phase spans' host seconds less the build
    seconds booked in them; ``<phase>_compile``: those build seconds."""
    out: Dict[str, float] = {}
    for phase in ("teacher", "tune", "student"):
        seconds, build = OT.totals(forest, f"walk/{phase}")
        out[phase] = max(seconds - build, 0.0)
        out[f"{phase}_compile"] = build
    return out


def pretrain(model, params, corpus, steps: int, batch: int, seq: int, lr: float,
             say=print):
    opt = adamw(lr)
    step = jax.jit(make_train_step(model.loss, opt))
    opt_state = opt.init(params)
    it = corpus_iterator(corpus, batch=batch, seq_len=seq, seed=1)
    loss = float("nan")
    for i in range(steps):
        params, opt_state, metrics, _ = step(
            params, opt_state, {"tokens": jnp.asarray(next(it))}, None
        )
        loss = float(metrics["loss"])
        if i % 20 == 0 or i == steps - 1:
            OM.series("pretrain/loss").append(loss, step=i)
    say(f"pretrained {steps} steps, final loss {loss:.3f}")
    return params


def main(argv=None, cfg: Optional[ModelConfig] = None) -> Dict[str, Any]:
    """Runs the pipeline; ``cfg`` (default ``get_config(--arch)``) lets a
    caller hand in a cut configuration. Returns the model, the tuned
    params, the masks, the per-block reports, the perplexities, the phase
    times and the run artifact (None under ``--no-obs``)."""
    use_compile_cache()
    spec = RunSpec.from_argv("ebft", argv)
    run = spec.start_obs_run()
    say = run.say if run is not None else print

    tuning.configure(mode=spec.kernel_tune,
                     path=spec.kernel_cache or None)
    tuning.reset_stats()

    plan = make_ebft_plan(spec.mesh_data, spec.mesh_model)
    if plan.active:
        say(f"calibration mesh: {plan.describe()['axes']} "
            f"({plan.device_count} devices)")

    cfg = cfg or get_config(spec.arch)
    model = build(cfg)
    corpus = SyntheticCorpus(CorpusConfig(vocab_size=cfg.vocab_size, seed=spec.seed))
    params = model.init(jax.random.PRNGKey(spec.seed))
    phases = {}
    ppl = {}

    if spec.kernel_tune != "off":
        # warm the tile-plan cache on the shapes this run's walk launches
        # (docs/PERF.md): in search mode this is where the measured sweeps
        # run — outside the timed hot path; in cache mode it is a free
        # readback whose hit/miss counts land in BENCH_ebft.json
        pat = tuple(int(x) for x in spec.pattern.split(":")) \
            if spec.pattern else None
        with _phase("phase/kernel_tune", mode=spec.kernel_tune) as sp:
            pretuned = tuning.pretune(
                tuning.ebft_workloads(cfg, tokens=8 * spec.seq, seq=spec.seq,
                                      pattern=pat),
                interpret=jax.default_backend() != "tpu",
            )
        phases["kernel_tune"] = sp.duration
        st = tuning.stats()
        say(f"kernel plans: {len(pretuned)} workloads, "
            f"{int(st['hits'])} cached, {int(st['searches'])} searched "
            f"({st['search_s']:.1f}s search)")

    if spec.pretrain_steps:
        with _phase("phase/pretrain", steps=spec.pretrain_steps) as sp:
            params = sp.fence(pretrain(model, params, corpus,
                                       spec.pretrain_steps, spec.batch,
                                       spec.seq, 3e-3, say=say))
        phases["pretrain"] = sp.duration

    calib = calibration_set(corpus, spec.calib_samples, spec.seq)
    ev = eval_set(corpus, 16, spec.seq)
    pattern = tuple(int(x) for x in spec.pattern.split(":")) if spec.pattern else None

    with _phase("phase/eval", what="dense") as sp:
        ppl["dense"] = perplexity(model, params, ev)
    phases["eval_dense"] = sp.duration
    say(f"dense ppl          {ppl['dense']:8.2f}")

    with _phase("phase/prune", method=spec.method,
                 sparsity=spec.sparsity) as sp:
        masks, pruned = prune(model, params, calib, method=spec.method,
                              sparsity=spec.sparsity, pattern=pattern)
        sp.fence(pruned)
    phases["prune"] = sp.duration
    ppl[spec.method] = perplexity(model, pruned, ev)
    say(f"{spec.method} ppl {' ' * (10 - len(spec.method))}"
        f"{ppl[spec.method]:8.2f}   ({phases['prune']:.0f}s)")

    ecfg = ebft.EBFTConfig(lr=spec.lr, epochs=spec.epochs,
                           fused_epochs=not spec.no_fused_epochs,
                           prefetch_depth=spec.prefetch_depth,
                           mesh_plan=plan)
    with _phase("phase/ebft", lr=spec.lr, epochs=spec.epochs) as sp:
        tuned, reports = ebft.finetune(model, params, pruned, masks, calib, ecfg)
        sp.fence(tuned)
    phases["ebft"] = sp.duration
    with _phase("phase/eval", what="ebft") as sp:
        ppl["EBFT"] = perplexity(model, tuned, ev)
    phases["eval_ebft"] = sp.duration
    mean_drop = sum(r.loss_before - r.loss_after for r in reports) \
        / max(len(reports), 1)
    say(f"EBFT ppl           {ppl['EBFT']:8.2f}   "
        f"({phases['ebft']:.0f}s, {len(reports)} blocks, "
        f"mean E drop {mean_drop:.3e})")

    wants = set(spec.baselines.split(",")) if spec.baselines else set()
    if "dsnot" in wants:
        with _phase("phase/baseline", which="dsnot") as sp:
            _, ds = prune(model, params, calib, method="dsnot",
                          sparsity=spec.sparsity, pattern=pattern,
                          dsnot_init=spec.method if spec.method != "dsnot" else "wanda")
            ppl["DSnoT"] = perplexity(model, ds, ev)
        phases["baseline_dsnot"] = sp.duration
        say(f"DSnoT ppl          {ppl['DSnoT']:8.2f}   ({sp.duration:.0f}s)")
    if "mask" in wants:
        with _phase("phase/baseline", which="mask") as sp:
            mt, _ = mask_tuning.finetune_masks(model, params, masks,
                                               spec.sparsity, calib, pattern=pattern)
            ppl["mask-tune"] = perplexity(model, mt, ev)
        phases["baseline_mask"] = sp.duration
        say(f"mask-tune ppl      {ppl['mask-tune']:8.2f}   ({sp.duration:.0f}s)")
    if "lora" in wants:
        with _phase("phase/baseline", which="lora") as sp:
            it = corpus_iterator(corpus, batch=8, seq_len=spec.seq, seed=9)
            lr_params = lora.finetune_lora(model, pruned, masks, it,
                                           lora.LoRAConfig(steps=200, lr=1e-3))
            ppl["LoRA"] = perplexity(model, lr_params, ev)
        phases["baseline_lora"] = sp.duration
        say(f"LoRA ppl           {ppl['LoRA']:8.2f}   ({sp.duration:.0f}s)")

    payload = None
    if run is not None:
        summ = OM.summary()
        peak = summ.get("ebft/live_block_bytes", {}).get("max")
        peak_shard = summ.get(
            "ebft/live_block_bytes_per_shard", {}).get("max")
        tune_max = max((r.dispatches for r in reports), default=0)
        sync_max = max((r.host_syncs for r in reports), default=0)
        fused_all = bool(reports) and all(r.path == "fused" for r in reports)
        path = spec.bench_out
        payload = run.finish(
            extra={
                "phases": phases,
                "blocks": [r.asdict() for r in reports],
                "perplexity": ppl,
                "ebft": {
                    "num_blocks": len(reports),
                    "mean_e_drop": mean_drop,
                    "peak_live_block_bytes": peak,
                    "fused_epochs": not spec.no_fused_epochs,
                    "prefetch_depth": spec.prefetch_depth,
                    "early_stops": {
                        reason: sum(1 for r in reports if r.early_stop == reason)
                        for reason in {r.early_stop for r in reports}
                    },
                },
                # device layout + wire accounting (docs/DISTRIBUTED.md):
                # inactive plans report devices=1 and zero collective bytes
                "mesh": {
                    **plan.describe(),
                    "peak_live_block_bytes_per_shard": peak_shard,
                    "collective_bytes_total": sum(
                        r.collective_bytes for r in reports),
                },
                # dispatch/host-sync accounting (docs/PERF.md): per-block =
                # tune-path dispatches + 2 stream advances (teacher+student)
                # in the fused/stacked walk; device_* = per participating
                # device (one SPMD launch enqueues on every mesh device)
                "dispatch": {
                    "tune_per_block_max": tune_max,
                    "tune_host_syncs_per_block_max": sync_max,
                    "per_block_max": tune_max + (2 if fused_all else 0),
                    "fused_all_blocks": fused_all,
                    "walk_total": summ.get("ebft/walk/dispatches", {}).get("value"),
                    "walk_host_syncs": summ.get(
                        "ebft/walk/host_syncs", {}).get("value"),
                    "device_dispatches_per_block": {
                        str(r.index): r.device_dispatches for r in reports
                    },
                    "tune_device_total": summ.get(
                        "ebft/tune/device_dispatches", {}).get("value"),
                    "walk_device_total": summ.get(
                        "ebft/walk/device_dispatches", {}).get("value"),
                },
                # per-phase host seconds of the walk, with the program-build
                # time JAX reported inside each phase's spans split out
                # (docs/PERF.md); device time is the profiler trace's
                "walk_phases": _walk_phases(run.tracer.tree()),
                # tile-plan autotuner accounting (docs/PERF.md): a warm
                # cache run must show misses == searches == 0 and
                # search_s == 0.0 (CI gates this via
                # `obs validate --require-cache-hits`)
                "kernel_tuning": {
                    "mode": spec.kernel_tune,
                    "cache_path": tuning.state()["path"],
                    **tuning.stats(),
                },
            },
            summary_path=path,
        )
        print(f"wrote {path}  (render with: python -m repro.obs report {path})")
    return {"model": model, "tuned": tuned, "masks": masks,
            "reports": reports, "perplexity": ppl, "phases": phases,
            "payload": payload}


if __name__ == "__main__":
    main()
