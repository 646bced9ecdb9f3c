"""Serving layer: batched generation and continuous batching scheduler."""
from __future__ import annotations

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.model import build
from repro.serving.decode import Request, Server


@pytest.fixture(scope="module")
def served():
    cfg = get_config("tiny_dense")
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def test_generate_batched_shapes(served):
    model, params = served
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 500, size=(16,)).astype(np.int32) for _ in range(3)]
    server = Server(model, params, batch_size=4, max_len=64)
    outs = server.generate(prompts, max_new=8)
    assert len(outs) == 3 and all(len(o) == 8 for o in outs)


def test_generate_deterministic_greedy(served):
    model, params = served
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 500, size=(12,)).astype(np.int32)]
    server = Server(model, params, batch_size=2, max_len=64)
    a = server.generate(prompts, max_new=6)
    b = server.generate(prompts, max_new=6)
    assert a == b


def test_continuous_batching_serves_all(served):
    model, params = served
    rng = np.random.default_rng(2)
    reqs = [
        Request(uid=i, prompt=rng.integers(0, 500, size=(10,)).astype(np.int32),
                max_new=4 + (i % 3))
        for i in range(7)
    ]
    server = Server(model, params, batch_size=3, max_len=64)
    results = server.serve(reqs)
    assert sorted(results) == list(range(7))
    for i, out in results.items():
        assert len(out) == 4 + (i % 3)


def test_sparse_params_serve_unchanged(served):
    """EBFT/pruned weights drop into the serving path (same pytree)."""
    from repro.core.masks import prune
    from repro.data.tokens import CorpusConfig, SyntheticCorpus, calibration_set

    model, params = served
    corpus = SyntheticCorpus(CorpusConfig(vocab_size=model.cfg.vocab_size))
    calib = calibration_set(corpus, 8, 32)
    _, pruned = prune(model, params, calib, method="wanda", sparsity=0.5)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 500, size=(8,)).astype(np.int32)]
    outs = Server(model, pruned, batch_size=1, max_len=32).generate(prompts, max_new=4)
    assert len(outs[0]) == 4


def test_serve_records_token_times(served):
    """One host timestamp per output id, in order, after admission."""
    model, params = served
    rng = np.random.default_rng(4)
    req = Request(uid=0, prompt=rng.integers(0, 500, size=(10,)).astype(np.int32),
                  max_new=6)
    out = Server(model, params, batch_size=2, max_len=64).serve([req])[0]
    assert len(req.token_ns) == len(out) == 6
    assert req.admitted_ns < req.token_ns[0]
    assert all(a < b for a, b in zip(req.token_ns, req.token_ns[1:]))
