"""The comparison that decides ``correct`` for a serving cell.

Once the window has closed, a sample of the requests it completed, drawn
from the seed and always holding the longest, is read three ways:

  replay        the sample served again through ``Server.serve``; greedy
                serving is deterministic, so each must serve the window's
                own ids (requests that differ: count)
  logit_err     the model's own ``prefill`` and ``decode_step`` (the
                entries that ``Server`` compiles, at the window's shapes:
                one sequence, a cache of ``max_len``) run over each
                prompt and then its served ids one by one, against the
                plain float32 reference over the same tokens: the largest
                |program logit - reference logit| at the served positions,
                over the largest reference logit there
  widest_gap    the largest gap by which a served token's reference logit
                lies below the reference's best at its position (logits)
  bad_ids       completed requests whose ids are not the requested number
                of ids inside the vocabulary (count)

Only ``Server.serve`` and the public ``Model`` entries are called; no
member of ``Server`` is read or replaced. The cell's limits file says
which numbers are compared.
"""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from harness import traffic

Done = Tuple[np.ndarray, int, List[int]]  # prompt, requested ids, served ids


def sample(done: List[Done], count: int, seed: int) -> List[Done]:
    """``count`` completed requests: the longest, then a seeded draw."""
    order = sorted(range(len(done)), key=lambda i: -(len(done[i][0]) + done[i][1]))
    rest = order[1:]
    rng = traffic.rng_for(seed, 4)
    picked = [order[0]] + [rest[i] for i in rng.permutation(len(rest))[: count - 1]]
    return [done[i] for i in picked]


def replay(picked: List[Done], serve) -> float:
    """Serves each picked request again through ``serve(prompt, n, uid)``;
    the count of those that served other ids than the window did."""
    return float(sum(list(serve(prompt, n, -2 - uid)) != list(ids)
                     for uid, (prompt, n, ids) in enumerate(picked)))


def program_logits(model, params, picked: List[Done], max_len: int,
                   vocab: int) -> List[np.ndarray]:
    """Per picked request, the logits (served ids, vocab) that the model's
    prefill and decode steps give at each served position, fed the
    prompt and then the served ids."""
    prefill, decode = jax.jit(model.prefill), jax.jit(model.decode_step)
    out = []
    for prompt, _, ids in picked:
        state = model.init_serve_state(1, max_len)
        logits, state = prefill(params, {"tokens": jnp.asarray(prompt[None])}, state)
        rows = [logits[0, -1, :vocab]]
        for t in ids[:-1]:
            logits, state = decode(params, jnp.full((1, 1), t, jnp.int32), state)
            rows.append(logits[0, -1, :vocab])
        out.append(np.asarray(jnp.stack(rows).astype(jnp.float32)))
    return out


def reference_logits(ref, conf, params, picked: List[Done], max_len: int) -> List[np.ndarray]:
    """Per picked request, the reference's logits at each served position."""
    V = conf["vocab_size"]
    toks = np.zeros((len(picked), max_len), np.int32)
    for row, (prompt, _, ids) in zip(toks, picked):
        seq = np.concatenate([prompt, np.asarray(ids[:-1], np.int32)])
        row[: len(seq)] = seq  # padding after the end is causally invisible
    logits = jax.jit(lambda p, t: ref.forward(p, t, conf))(params, jnp.asarray(toks))
    out = []
    for r, (prompt, _, ids) in enumerate(picked):
        start = len(prompt) - 1
        out.append(np.asarray(logits[r, start: start + len(ids), :V]))
    return out


def numbers(picked: List[Done], replayed: float, prog: List[np.ndarray],
            refs: List[np.ndarray], done: List[Done], vocab: int) -> Dict[str, Any]:
    """A NaN anywhere in the program's logits reads as NaN."""
    err, scale, gap = [], [], []
    for (_, _, ids), lp, lr in zip(picked, prog, refs):
        err.append(np.max(np.abs(lp - lr)))
        scale.append(np.max(np.abs(lr)))
        served = lr[np.arange(len(ids)), np.asarray(ids)]
        gap.append(np.max(np.max(lr, axis=-1) - served))
    bad = sum(1 for _, n, ids in done
              if len(ids) != n or any(not 0 <= t < vocab for t in ids))
    return {"logit_err": float(np.max(err) / np.max(scale)),
            "widest_gap": float(np.max(gap)), "replay": replayed, "bad_ids": float(bad)}
