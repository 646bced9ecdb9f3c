"""Batched serving driver: prefill + decode with continuous batching.

    python -m repro.launch.serve --arch tiny_dense --requests 12 \
        --slots 4 --prompt-len 32 --max-new 16 [--sparse 0.5]

``--sparse`` prunes the (randomly initialised or checkpointed) model with
Wanda and serves the sparse weights — demonstrating that EBFT-fine-tuned
sparse params drop into the serving path unchanged (same pytree).

Flags are one view of :class:`repro.launch.api.RunSpec`; ``--slots``
names the continuous-batching decode slots (the old ``--batch`` spelling
parses through the deprecation shim).
"""
from __future__ import annotations

import time
from typing import Any, Dict, Optional

import jax
import numpy as np

from repro.checkpoint import ckpt as CK
from repro.configs import get_config
from repro.configs.base import ModelConfig
from repro.core.masks import prune
from repro.data.tokens import CorpusConfig, SyntheticCorpus, calibration_set
from repro.launch.api import RunSpec
from repro.launch.compile_cache import use_compile_cache
from repro.models.model import build
from repro.obs import metrics as OM
from repro.serving.decode import Request, Server


def main(argv=None, cfg: Optional[ModelConfig] = None) -> Dict[str, Any]:
    """Serves the requests; ``cfg`` (default ``get_config(--arch)``) lets a
    caller hand in a cut configuration. Returns the model, the served
    params, the generated ids per request, the serving wall time and the
    run artifact (None under ``--no-obs``)."""
    use_compile_cache()
    spec = RunSpec.from_argv("serve", argv)
    run = spec.start_obs_run()

    cfg = cfg or get_config(spec.arch)
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(spec.seed))
    if spec.ckpt_dir:
        latest = CK.latest_step(spec.ckpt_dir)
        if latest is not None:
            params = CK.restore(spec.ckpt_dir, {"params": params})["params"]
            print(f"loaded checkpoint step {latest}")

    corpus = SyntheticCorpus(CorpusConfig(vocab_size=cfg.vocab_size, seed=spec.seed))
    if spec.sparse > 0:
        calib = calibration_set(corpus, 16, spec.prompt_len)
        _, params = prune(model, params, calib, method="wanda", sparsity=spec.sparse)
        print(f"serving wanda-pruned weights at sparsity {spec.sparse}")

    rng = np.random.default_rng(spec.seed)
    reqs = [
        Request(uid=i, prompt=corpus.sample(rng, spec.prompt_len),
                max_new=spec.max_new)
        for i in range(spec.requests)
    ]
    server = Server(model, params, batch_size=spec.slots,
                    max_len=spec.max_len, temperature=spec.temperature)
    t0 = time.perf_counter()
    results = server.serve(reqs)
    dt = time.perf_counter() - t0
    toks = sum(len(v) for v in results.values())
    print(f"served {len(results)} requests, {toks} tokens in {dt:.1f}s "
          f"({toks / max(dt, 1e-9):.1f} tok/s, continuous batching over "
          f"{spec.slots} slots)")
    for uid in sorted(results)[:3]:
        print(f"  req {uid}: {results[uid][:8]}...")
    payload = None
    if run is not None:
        occ = OM.summary().get("serve/batch_occupancy", {})
        print(f"  mean batch occupancy "
              f"{(occ.get('mean') or 0.0) * 100:.0f}% over {spec.slots} slots")
        payload = run.finish(
            extra={"served": {"requests": len(results), "tokens": toks,
                              "tokens_per_s": toks / max(dt, 1e-9)}},
            summary_path=spec.bench_out or None)
    return {"model": model, "params": params, "results": results,
            "seconds": dt, "payload": payload}


if __name__ == "__main__":
    main()
