"""The traffic generator: every input of a run, drawn from ``--seed``.

Token text comes from a synthetic corpus with the statistics of
``repro.data.tokens.SyntheticCorpus`` (a Zipf unigram backbone, a
low-rank first-order Markov tilt, and injected template n-grams), copied
here so the yardstick cannot move with the program, and vectorised
across rows so that making a calibration set costs milliseconds, not the
per-token ``rng.choice`` of the original. The corpus's structure (its
"language") comes from the traffic file's ``corpus.seed``; which text is
drawn from it comes from the run's seed.

A traffic file sets the sizes; the seed only orders and fills them, so
every seed does the same amount of work.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Tuple

import numpy as np


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """An independent stream per (seed, purpose); any non-negative int."""
    return np.random.default_rng([int(seed), *[int(s) for s in stream]])


def jax_key(seed: int, stream: int = 0):
    """A JAX PRNG key from a seed of any size (the driver's exceed 2**31)."""
    import jax

    words = np.random.SeedSequence([int(seed), int(stream)]).generate_state(2)
    key = jax.random.PRNGKey(int(words[0] & 0x7FFFFFFF))
    return jax.random.fold_in(key, int(words[1] & 0x7FFFFFFF))


class Corpus:
    """Zipf + low-rank Markov + templates over ``vocab`` ids."""

    def __init__(self, vocab: int, zipf_a: float = 1.2, markov_rank: int = 16,
                 markov_weight: float = 0.55, n_templates: int = 64,
                 template_len: int = 8, template_rate: float = 0.05,
                 seed: int = 0):
        self.vocab = V = int(vocab)
        self.markov_weight = markov_weight
        self.template_rate = template_rate
        rng = np.random.default_rng(seed)
        uni = np.arange(1, V + 1, dtype=np.float64) ** (-zipf_a)
        uni /= uni.sum()
        self.tok2cluster = rng.integers(0, markov_rank, size=V)
        tilt = rng.dirichlet(np.full(V, 0.05), size=markov_rank)
        nxt = markov_weight * (0.5 * tilt + 0.5 * uni[None, :]) \
            + (1.0 - markov_weight) * uni[None, :]
        nxt /= nxt.sum(-1, keepdims=True)
        # one flat CDF: row r occupies (r, r + 1], so a single searchsorted
        # samples every row's own cluster distribution at once
        cdf = np.cumsum(nxt, axis=-1)
        cdf[:, -1] = 1.0
        self._cdf = (cdf + np.arange(markov_rank)[:, None]).ravel()
        ucdf = np.cumsum(uni)
        ucdf[-1] = 1.0
        self._ucdf = ucdf
        self.templates = rng.integers(0, max(2, V // 8),
                                      size=(n_templates, template_len))

    def sample(self, rng: np.random.Generator, rows: int, length: int) -> np.ndarray:
        """(rows, length) int32 token ids."""
        V = self.vocab
        T, L = self.templates.shape
        out = np.empty((rows, length), np.int32)
        prev = np.searchsorted(self._ucdf, rng.random(rows), side="right")
        tmpl = np.zeros(rows, np.int64)      # template being copied
        left = np.zeros(rows, np.int64)      # its tokens still to copy
        for t in range(length):
            start = (left == 0) & (rng.random(rows) < self.template_rate)
            tmpl = np.where(start, rng.integers(0, T, size=rows), tmpl)
            left = np.where(start, L, left)
            copying = left > 0
            c = self.tok2cluster[np.minimum(prev, V - 1)]
            drawn = np.searchsorted(self._cdf, c + rng.random(rows),
                                    side="right") - c * V
            tok = np.where(copying, self.templates[tmpl, (L - left) % L], drawn)
            tok = np.clip(tok, 0, V - 1)
            left = np.where(copying, left - 1, left)
            out[:, t] = tok
            prev = tok
        return out


def corpus(traffic: Dict[str, Any], vocab: int) -> Corpus:
    return Corpus(vocab, **traffic.get("corpus", {}))


def calibration_sets(traffic: Dict[str, Any], vocab: int, seed: int,
                     count: int) -> List[np.ndarray]:
    """``count`` calibration sets of (samples, seq_len) tokens: set k is
    stream k of the seed, so set 0 is the same however many are drawn."""
    cal = traffic["calibration"]
    c = corpus(traffic, vocab)
    return [c.sample(rng_for(seed, 1, k), cal["samples"], cal["seq_len"])
            for k in range(count)]


def mask_calibration(traffic: Dict[str, Any], vocab: int, seed: int) -> np.ndarray:
    """The tokens the benchmark's Wanda masks are scored on."""
    pr = traffic["prune"]
    return corpus(traffic, vocab).sample(rng_for(seed, 2), pr["samples"],
                                         pr["seq_len"])


def output_lengths(spec: Dict[str, Any], n: int) -> List[int]:
    """``n`` output lengths at the mid-quantiles of a lognormal (``median``,
    ``sigma``) clipped to [min, max]: a fixed set, the same for every seed."""
    from statistics import NormalDist

    nd = NormalDist()
    mu = math.log(spec["median"])
    out = []
    for i in range(n):
        z = nd.inv_cdf((i + 0.5) / n)
        v = int(round(math.exp(mu + spec["sigma"] * z)))
        out.append(min(max(v, spec["min"]), spec["max"]))
    return out


def request_cycle(traffic: Dict[str, Any]) -> List[Tuple[int, int]]:
    """One cycle's (prompt_len, output_len) multiset, before ordering:
    ``prompt_counts[i]`` prompts of ``prompt_lengths[i]``, and the output
    lengths of :func:`output_lengths` for as many requests."""
    cyc = traffic["cycle"]
    prompts = [p for p, k in zip(cyc["prompt_lengths"], cyc["prompt_counts"])
               for _ in range(k)]
    outs = output_lengths(cyc["output"], len(prompts))
    return list(zip(prompts, outs))


def requests(traffic: Dict[str, Any], vocab: int, seed: int,
             cycles: int) -> List[Tuple[np.ndarray, int]]:
    """``cycles`` cycles of (prompt tokens, output length). Each cycle holds
    the same multiset of sizes; the seed pairs, orders and fills them."""
    base = request_cycle(traffic)
    prompts = sorted(p for p, _ in base)
    outs = sorted(o for _, o in base)
    c = corpus(traffic, vocab)
    out: List[Tuple[np.ndarray, int]] = []
    for k in range(cycles):
        rng = rng_for(seed, 3, k)
        ps = [prompts[i] for i in rng.permutation(len(prompts))]
        os_ = [outs[i] for i in rng.permutation(len(outs))]
        text = c.sample(rng, len(ps), max(ps))  # one draw for the cycle
        for row, p, o in zip(text, ps, os_):
            out.append((row[:p].copy(), o))
    return out
