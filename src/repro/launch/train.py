"""End-to-end training driver.

Runs a real (allocating) training loop on whatever devices exist — the
same code path scales from the 1-CPU container (tiny/small configs, the
quickstart) to a pod slice (assigned configs): the mesh is sized from
``jax.device_count()`` and every step is the sharded step from
launch/steps.py.

    python -m repro.launch.train --arch tiny_dense --steps 200 \
        --batch 32 --seq 128 --ckpt-dir /tmp/ckpt

Fault tolerance in action: if ``--ckpt-dir`` has a checkpoint, training
RESUMES from it (elastic: the restore reshards to the current mesh). Kill
the process mid-run and relaunch to exercise it.

Flags are one view of :class:`repro.launch.api.RunSpec`; the mesh axes
are ``--mesh-data``/``--mesh-model`` (the old spellings parse through
the deprecation shim).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import ckpt as CK
from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.data.tokens import CorpusConfig, SyntheticCorpus
from repro.distributed import sharding as SH
from repro.launch import steps as ST
from repro.launch.api import RunSpec
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_debug_mesh
from repro.models.model import build
from repro.obs.profile import profiled
from repro.optim.optimizers import adamw
from repro.optim.schedules import warmup_cosine
from repro.training.train_loop import Trainer, make_train_step


def main(argv=None) -> None:
    use_compile_cache()
    spec = RunSpec.from_argv("train", argv)
    run = spec.start_obs_run()

    cfg = get_config(spec.arch)
    model = build(cfg)
    ndev = jax.device_count()
    data = spec.mesh_data or (ndev // spec.mesh_model)
    mesh = make_debug_mesh(data, spec.mesh_model)
    print(f"devices={ndev} mesh={dict(zip(mesh.axis_names, mesh.devices.shape))}")

    corpus = SyntheticCorpus(CorpusConfig(vocab_size=cfg.vocab_size, seed=spec.seed))
    shape = ShapeConfig("cli", spec.seq, spec.batch, "train")

    rng = jax.random.PRNGKey(spec.seed)
    with mesh:
        params = model.init(rng)
        pspecs = SH.param_pspecs(params, mesh)
        params = jax.device_put(params, SH.named(pspecs, mesh))
        opt = adamw(warmup_cosine(spec.lr, warmup=20, total=max(spec.steps, 21)))
        opt_state = opt.init(params)

        err_state = None
        step_fn = make_train_step(
            model.loss, opt, microbatches=spec.microbatches,
            compress_ratio=spec.compress,
        )
        if spec.compress < 1.0:
            from repro.optim.grad_compress import init_error_state
            err_state = init_error_state(params)
        # profiled: records compile time vs execution time (no-op when off)
        jitted = profiled(jax.jit(step_fn), "train/step")

        # deterministic data order: batch is a pure function of step, so any
        # host can recompute it after restart (straggler/fault tolerance).
        def data_fn(step: int):
            r = np.random.default_rng((spec.seed << 20) + step)
            toks = np.stack([
                corpus.sample(r, spec.seq) for _ in range(spec.batch)
            ])
            batch = {"tokens": jnp.asarray(toks)}
            if cfg.family == "vlm":
                in_specs = model.input_specs(shape)
                P = in_specs["patches"].shape[1]
                batch["tokens"] = batch["tokens"][:, : spec.seq - P]
                batch["patches"] = jnp.asarray(
                    r.normal(size=(spec.batch, P, cfg.d_model)).astype(np.float32)
                )
            if cfg.family == "encdec":
                F = model.input_specs(shape)["frames"].shape[1]
                batch["frames"] = jnp.asarray(
                    r.normal(size=(spec.batch, F, cfg.d_model)).astype(np.float32)
                )
            return batch

        start = 0
        if spec.ckpt_dir:
            latest = CK.latest_step(spec.ckpt_dir)
            if latest is not None:
                tree = CK.restore(
                    spec.ckpt_dir, {"params": params, "opt_state": opt_state},
                    step=latest,
                )
                params, opt_state = tree["params"], tree["opt_state"]
                start = latest
                print(f"resumed from step {start}")

        trainer = Trainer(
            step_fn=jitted,
            data_fn=data_fn,
            ckpt_dir=spec.ckpt_dir or None,
            ckpt_every=spec.ckpt_every,
            log_every=10,
        )
        t0 = time.perf_counter()
        params, opt_state, history = trainer.run(
            params, opt_state, start, spec.steps - start, err_state
        )
        CK.wait_all()
        dt = time.perf_counter() - t0
        for s, l in history[-5:]:
            print(f"step {s:5d} loss {l:.4f}")
        print(f"{spec.steps - start} steps in {dt:.1f}s "
              f"({(spec.steps - start) / max(dt, 1e-9):.2f} steps/s)")
        if run is not None:
            run.finish(
                extra={"trained": {"steps": spec.steps - start, "seconds": dt,
                                   "steps_per_s": (spec.steps - start) / max(dt, 1e-9),
                                   "history": history}},
                summary_path=spec.bench_out or None,
            )


if __name__ == "__main__":
    main()
