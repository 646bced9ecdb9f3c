"""The EBFT walk as a user runs it: ``repro.core.ebft.finetune`` over a
pruned model and a calibration set.

Set-up makes the dense weights on the device from the seed, the Wanda
masks, and as many calibration sets as the traffic file asks for, then
makes the first ``finetune`` call: the window's own call on the window's
sizes, which compiles or loads every program the walk runs and whose
result the reference check follows. The window is whole ``finetune``
calls begun before ``--seconds`` ran out, each on its own calibration
set; its end-to-end metric is the window's time over the blocks tuned.
"""
from __future__ import annotations

import math
import time

import jax
import jax.numpy as jnp

from harness import traffic, walkcheck, wanda


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        self.tr = ctx.traffic
        e = self.tr["ebft"]
        self.ecfg = {"lr": e["lr"], "epochs": e["epochs"],
                     "microbatch": e["microbatch"], "patience": e["patience"],
                     "rel_tol": e["rel_tol"]}

    def _finetune(self, calib):
        from repro.core import ebft

        ecfg = ebft.EBFTConfig(**self.ecfg)
        with self.ctx.annotate("finetune"):
            tuned, reports = ebft.finetune(self.ctx.model, self.params_p, self.pruned_p,
                                           self.masks, calib, ecfg)
            jax.block_until_ready(tuned)
        return tuned, reports

    def setup(self):
        ctx, tr = self.ctx, self.tr
        conf, ref = ctx.conf, ctx.ref
        vocab = conf["vocab_size"]
        self.params = jax.jit(lambda k: ref.init(k, conf))(traffic.jax_key(ctx.seed))
        self.calib = traffic.calibration_sets(tr, vocab, ctx.seed,
                                              tr["window"]["calibration_sets"])
        pr = tr["prune"]
        self.masks, self.pruned = wanda.masks(
            ref, conf, self.params, traffic.mask_calibration(tr, vocab, ctx.seed),
            pr["sparsity"], self.ecfg["microbatch"])
        # the program runs in the configuration's dtype (a precision
        # control switches the program's own lower-precision path on)
        dt = jnp.dtype(ctx.cfg.param_dtype)
        self.params_p = jax.tree.map(lambda a: a.astype(dt), self.params)
        self.pruned_p = jax.tree.map(lambda a: a.astype(dt), self.pruned)
        tuned, reports = self._finetune(self.calib[0])
        self.first = walkcheck.program_summary(ref, tuned, self.pruned_p, self.masks, reports)
        del tuned

    def window(self, seconds: float):
        t0 = time.perf_counter()
        calls = failed = blocks = 0
        epochs = []
        self.last = None
        while calls == 0 or time.perf_counter() - t0 < seconds:
            self.last = None
            k = 1 + calls % (len(self.calib) - 1)
            self.last, reports = self._finetune(self.calib[k])
            calls += 1
            blocks += len(reports)
            epochs += [(r.index, r.epochs_run) for r in reports]
            failed += any(not (math.isfinite(r.loss_before) and math.isfinite(r.loss_after))
                          for r in reports)
        dt = time.perf_counter() - t0
        cal = self.tr["calibration"]
        return {"attempted": calls, "failed": failed,
                "metrics": {"walk_block_s": dt / blocks},
                "counts": {"calls": calls, "blocks_tuned": blocks, "epochs_run": epochs,
                           "seq_len": cal["seq_len"],
                           "tokens": cal["samples"] * cal["seq_len"]}}

    def free(self):
        last = getattr(self, "last", None)
        self.nonzero_last = 0 if last is None else int(
            walkcheck.masked_nonzero(last, self.masks))
        self.last = self.params_p = self.pruned_p = None

    def check(self):
        ctx = self.ctx
        ref = walkcheck.reference(ctx.ref, ctx.conf, self.params, self.pruned, self.masks,
                                  self.calib[0], self.ecfg)
        self.notes = {"dropped": walkcheck.dropped(ref),
                      "per_block": walkcheck.by_block(self.first, ref)}
        return walkcheck.numbers(self.first, ref, self.nonzero_last)
