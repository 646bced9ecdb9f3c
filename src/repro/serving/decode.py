"""Batched serving: prefill + decode loops with continuous batching.

``Server`` wraps a Model with jitted prefill/decode steps and a minimal
continuous-batching scheduler: a fixed pool of B slots; finished sequences
free their slot and queued requests are prefilled into it. The KV cache is
allocated once (B, max_len) and slots are recycled — the paper-relevant
part is that sparse (EBFT-fine-tuned) weights drop straight in, since the
serve path reads the same param pytree as training.

A decode step is one jitted program, sampling included (greedy or
temperature), and the host reads each step's ids in one transfer. While
every active slot needs another id, the next step is dispatched before
that read, so the device runs the steps back to back.
Everything is jit-compiled once per (batch, len) bucket.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import metrics as OM
from repro.obs import trace as OT


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray  # (S,) int32
    max_new: int = 32
    out: Optional[List[int]] = None
    # host time.perf_counter_ns() when the request left the queue, and
    # when each id of ``out`` reached the host (one per id, in order)
    admitted_ns: int = 0
    token_ns: Optional[List[int]] = None


class Server:
    def __init__(self, model, params, batch_size: int, max_len: int, temperature: float = 0.0):
        self.model = model
        self.params = params
        self.B = batch_size
        self.max_len = max_len
        self.temperature = temperature

        self._prefill = jax.jit(model.prefill)
        # one program per decode step, rng split to sampled ids; a lambda,
        # so it lowers as ``jit__lambda_``
        self._decode = jax.jit(lambda p, t, s, r: self._step(p, t, s, r))

    def _sample(self, logits: jax.Array, rng) -> jax.Array:
        # the head is padded to a multiple of 128 rows; ids past the
        # vocabulary are not tokens and are never sampled
        logits = logits[:, -1, : self.model.cfg.vocab_size]
        if self.temperature <= 0:
            return jnp.argmax(logits, axis=-1)
        return jax.random.categorical(rng, logits / self.temperature, axis=-1)

    def _step(self, params, tok, state, rng):
        """(B, 1) int32 ids -> (the next (B, 1) int32 ids, state, rng)."""
        rng, sub = jax.random.split(rng)
        logits, state = self.model.decode_step(params, tok, state)
        return self._sample(logits, sub)[:, None].astype(jnp.int32), state, rng

    def generate(self, prompts: List[np.ndarray], max_new: int = 32, seed: int = 0):
        """One-shot batched generation (prompts padded to a bucket)."""
        assert len(prompts) <= self.B
        B = len(prompts)
        S = max(len(p) for p in prompts)
        toks = np.zeros((B, S), np.int32)
        for i, p in enumerate(prompts):
            toks[i, S - len(p):] = p  # left-pad so last position aligns
        state = self.model.init_serve_state(B, S + max_new)
        batch = {"tokens": jnp.asarray(toks)}
        logits, state = self._prefill(self.params, batch, state)
        rng = jax.random.PRNGKey(seed)
        tok = self._sample(logits, rng)[:, None].astype(jnp.int32)
        cols = [tok]
        for _ in range(max_new - 1):
            tok, state, rng = self._decode(self.params, tok, state, rng)
            cols.append(tok)
        return np.asarray(jnp.concatenate(cols, axis=1))[:, :max_new].tolist()

    # ------------------------------------------------------------------
    def serve(self, requests: List[Request], seed: int = 0) -> Dict[int, List[int]]:
        """Continuous batching: slots are freed as sequences finish and
        refilled from the queue. Single-slot prefill keeps the example
        simple; a production server would bucket prefills."""
        queue = list(requests)
        results: Dict[int, List[int]] = {}
        active: List[Optional[Request]] = [None] * self.B
        remaining = np.zeros(self.B, np.int64)
        state = self.model.init_serve_state(self.B, self.max_len)
        last_tok = jnp.zeros((self.B, 1), jnp.int32)
        rng = jax.random.PRNGKey(seed)
        obs_on = OT.enabled()
        tokens_out = 0
        t_start = time.perf_counter()

        def admit():
            nonlocal state, last_tok
            for slot in range(self.B):
                while active[slot] is None and queue:
                    req = queue.pop(0)
                    with OT.span("serve/admit", uid=req.uid):
                        req.admitted_ns = time.perf_counter_ns()
                        active[slot] = req
                        req.out = []
                        req.token_ns = []
                        remaining[slot] = req.max_new
                        # single-sequence prefill into this slot
                        sub = self.model.init_serve_state(1, self.max_len)
                        logits, sub = self._prefill(
                            self.params, {"tokens": jnp.asarray(req.prompt[None])},
                            sub
                        )
                        state = jax.tree.map(
                            lambda full, one: _slot_update(full, one, slot),
                            state, sub
                        )
                        tok = int(jnp.argmax(
                            logits[0, -1, : self.model.cfg.vocab_size]))
                        req.token_ns.append(time.perf_counter_ns())
                        req.out.append(tok)
                        last_tok = last_tok.at[slot, 0].set(tok)
                        remaining[slot] -= 1
                        if remaining[slot] <= 0:  # the prefill's id was all
                            results[req.uid] = req.out
                            active[slot] = None
            if obs_on:
                OM.gauge("serve/queue_depth").set(len(queue))

        # every host statement of a decode iteration sits in serve/step
        # (serve/sync: the token read); admissions are serve/admit. While
        # every active slot needs another token after this step, the next
        # step is dispatched before this one's read ("ahead"), so the chip
        # runs steps back to back; otherwise the read, the freed slots and
        # the admissions come first
        with OT.span("serve/batch", requests=len(requests), slots=self.B):
            admit()
            nxt = None  # (B, 1) ids of a step dispatched ahead, not yet read
            while any(a is not None for a in active):
                ahead = all(remaining[slot] > 1
                            for slot, req in enumerate(active) if req is not None)
                with OT.span("serve/step", ahead=int(ahead)):
                    if obs_on:
                        # occupancy: fraction of slots doing useful decode work
                        OM.histogram("serve/batch_occupancy").observe(
                            sum(1 for a in active if a is not None) / self.B
                        )
                        if ahead:
                            OM.counter("serve/steps_ahead").inc()
                    if nxt is None:  # the step before did not dispatch this one
                        nxt, state, rng = self._decode(self.params, last_tok, state, rng)
                    last_tok = nxt
                    if ahead:
                        nxt, state, rng = self._decode(self.params, last_tok, state, rng)
                    else:
                        nxt = None
                    with OT.span("serve/sync"):
                        ids = np.asarray(last_tok)  # obs: sync-ok (the step's ids)
                        t = time.perf_counter_ns()
                        for slot in range(self.B):
                            req = active[slot]
                            if req is None:
                                continue
                            req.token_ns.append(t)
                            req.out.append(int(ids[slot, 0]))
                            remaining[slot] -= 1
                            tokens_out += 1
                            if remaining[slot] <= 0:
                                results[req.uid] = req.out
                                active[slot] = None
                admit()
            if obs_on:
                dt = time.perf_counter() - t_start
                tokens_out += len(results)  # one prefill token per request
                OM.counter("serve/tokens").inc(tokens_out)
                OM.counter("serve/requests").inc(len(results))
                OM.gauge("serve/tokens_per_s").set(tokens_out / max(dt, 1e-9))
        return results


def _slot_update(full: jax.Array, one: jax.Array, slot: int) -> jax.Array:
    """Write a single-sequence state into batch slot ``slot``. Batch dim is
    the first dim where shapes differ (full=B, one=1); scalars merge by max
    (the shared ``len`` counter)."""
    if full.ndim == 0:
        return jnp.maximum(full, one)
    for axis in range(full.ndim):
        if full.shape[axis] != one.shape[axis]:
            idx = [slice(None)] * full.ndim
            idx[axis] = slice(slot, slot + 1)
            return full.at[tuple(idx)].set(one.astype(full.dtype))
    return one.astype(full.dtype)  # identical shapes: shared state
