"""Single-stream serving: one request per ``Server.serve`` call, one slot,
greedy, closed loop (the next request is sent when the last is done).

Set-up makes the dense weights on the device from the seed, prunes them
with the benchmark's Wanda masks, builds one ``Server`` and serves one
short request at each prompt length of the mix, which compiles or loads
every program the window runs. The window sends requests in the seeded
order until ``--seconds`` has run out and the request in flight has
completed; its end-to-end metric is the output tokens of all completed
requests over the window's time.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from harness import servecheck, traffic, wanda


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        self.tr = ctx.traffic

    def setup(self):
        from repro.serving.decode import Server

        ctx, tr = self.ctx, self.tr
        conf, ref = ctx.conf, ctx.ref
        vocab = conf["vocab_size"]
        params = jax.jit(lambda k: ref.init(k, conf))(traffic.jax_key(ctx.seed))
        pr = tr["prune"]
        _, self.params = wanda.masks(ref, conf, params,
                                     traffic.mask_calibration(tr, vocab, ctx.seed),
                                     pr["sparsity"], pr["microbatch"])
        del params
        dt = jnp.dtype(ctx.cfg.param_dtype)
        self.served = jax.tree.map(lambda a: a.astype(dt), self.params)
        self.server = Server(ctx.model, self.served, batch_size=tr["slots"],
                             max_len=tr["max_len"], temperature=0.0)
        self.requests = traffic.requests(tr, vocab, ctx.seed, tr["cycles"])
        for p in tr["cycle"]["prompt_lengths"]:  # every prefill shape, and decode
            prompt = next(r[0] for r in self.requests if len(r[0]) == p)
            self._serve(prompt, 2, uid=-1)

    def _serve(self, prompt, n, uid):
        from repro.serving.decode import Request

        with self.ctx.annotate("serve"):
            return self.server.serve([Request(uid=uid, prompt=prompt, max_new=n)])[uid]

    def window(self, seconds: float):
        t0 = time.perf_counter()
        self.done = []
        i = 0
        while i == 0 or time.perf_counter() - t0 < seconds:
            prompt, n = self.requests[i % len(self.requests)]
            self.done.append((prompt, n, self._serve(prompt, n, uid=i)))
            i += 1
        dt = time.perf_counter() - t0
        tokens = sum(len(ids) for _, _, ids in self.done)
        return {"attempted": i, "failed": 0,
                "metrics": {"serve_tokens_per_s": tokens / dt},
                "counts": {"requests": [(len(p), n) for p, n, _ in self.done],
                           "tokens": tokens}}

    def free(self):
        """Replays the check's sample through the server and reads the
        model's logits over it, then drops the program's state."""
        ctx = self.ctx
        self.picked = servecheck.sample(self.done, self.tr["check_requests"], ctx.seed)
        self.replayed = servecheck.replay(self.picked, self._serve)
        self.prog = servecheck.program_logits(ctx.model, self.served, self.picked,
                                              self.tr["max_len"], ctx.conf["vocab_size"])
        self.server = self.served = None

    def check(self):
        ctx = self.ctx
        refs = servecheck.reference_logits(ctx.ref, ctx.conf, self.params, self.picked,
                                           self.tr["max_len"])
        return servecheck.numbers(self.picked, self.replayed, self.prog, refs, self.done,
                                  ctx.conf["vocab_size"])
