#!/usr/bin/env python3
"""Smoke run of the main path on TPU: prune, the block-wise EBFT walk, and
serving the sparse model, through the normal entry points
(``repro.launch.ebft_run.main`` and ``repro.launch.serve.main``), in one
process, on random weights made from a seed.

    python chip_smoke.py              # one chip: walk, then serving
    python chip_smoke.py --chips 4    # the walk on a 2x2 mesh against the
                                      # same walk on one device of the host

It checks results, not speed: the times it prints are one smoke run's,
not a benchmark. It fails (non-zero exit, no result line) when JAX finds
no TPU, when any check fails, or when the repository's ``src/`` is not
beside it. The last line of a passing run is one JSON object naming the
device as JAX reports it.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "qwen1_5_4b"
# One chip's share (model-configs guide section 4): every width as
# published, 4 of 40 layers, and the vocabulary slice that one of 8 chips
# would hold with the embedding and head sharded over the model axis.
CUT = {"num_layers": 4, "vocab_size": 151936 // 8}
CUT_WHY = ("four float32 trees of the model (params, pruned, masks, "
           "student) live at once in the walk; at the full vocabulary two "
           "layers already fill the chip's 16 GB")
OUT_DIR = os.path.join(ROOT, "experiments", "chip_smoke")

# Prefill + one decode step against the full forward pass, both at
# "highest" matmul precision: the two paths differ only in summation
# order (cached, chunked attention against one causal pass), which f32
# keeps near 1e-6 of the logit scale. A single bf16 pass rounds every
# operand to 8 mantissa bits (relative error up to 4e-3), so a path
# computing below the configured float32 would fail 1e-4.
DECODE_RTOL = 1e-4
# Per-block loss after tuning, 2x2 mesh against one device: the mesh
# reorders the gradient all-reduce and the matmul reductions, and two
# epochs of Adam carry that rounding into the weights.
MESH_RTOL = 1e-3


def say(line: str) -> None:
    print(line, flush=True)


def peak_bytes(device) -> int:
    return int(device.memory_stats()["peak_bytes_in_use"])


def prunable_zero_counts(tuned, masks):
    """(path, zeros in the tuned weight, zeros in its mask, tuned nonzeros
    where the mask is zero, size) for every prunable leaf."""
    import jax
    import jax.numpy as jnp

    from repro.sparsity.sparse_params import _path_names, is_prunable

    out = []

    def visit(path, w, m):
        if is_prunable(path, w):
            out.append(("/".join(_path_names(path)), jnp.sum(w == 0),
                        jnp.sum(m == 0), jnp.sum((m == 0) & (w != 0)), w.size))
        return w

    jax.tree_util.tree_map_with_path(visit, tuned, masks)
    return [(p, int(zw), int(zm), int(bad), n) for p, zw, zm, bad, n
            in jax.device_get(out)]


def walk(cfg, *, mesh=(1, 1), seq=1024, calib=64, epochs=2, sparsity=0.7,
         out_dir=OUT_DIR):
    """Phase 1: ``ebft_run.main`` at ``cfg``; checks losses, perplexities
    and that every tuned prunable weight is zero exactly where its mask
    is. Returns the per-block losses after tuning and the tuned params."""
    from repro.launch import ebft_run

    argv = ["--arch", ARCH, "--pretrain-steps", "0", "--method", "wanda",
            "--sparsity", str(sparsity), "--calib-samples", str(calib),
            "--seq", str(seq), "--epochs", str(epochs),
            # the paper's Adam step size; the launcher's 1e-2 default
            # (sized for tiny pretrained models) diverges at full width
            "--lr", "2e-4",
            "--mesh-data", str(mesh[0]), "--mesh-model", str(mesh[1]),
            "--bench-out", os.path.join(
                out_dir, f"BENCH_ebft_{mesh[0]}x{mesh[1]}.json")]
    t0 = time.perf_counter()
    res = ebft_run.main(argv, cfg=cfg)
    wall = time.perf_counter() - t0

    reports = res["reports"]
    if len(reports) != cfg.num_layers:
        raise AssertionError(f"{len(reports)} block reports for "
                             f"{cfg.num_layers} layers")
    for r in reports:
        if not (math.isfinite(r.loss_before) and math.isfinite(r.loss_after)):
            raise AssertionError(f"block {r.index}: non-finite loss "
                                 f"{r.loss_before} -> {r.loss_after}")
        if not r.loss_after < r.loss_before:
            raise AssertionError(f"block {r.index}: tuning did not lower "
                                 f"the loss ({r.loss_before} -> "
                                 f"{r.loss_after})")
    for name, value in res["perplexity"].items():
        if not math.isfinite(value):
            raise AssertionError(f"{name} perplexity is {value}")

    counts = prunable_zero_counts(res["tuned"], res["masks"])
    for path, zw, zm, bad, n in counts:
        if bad or zw != zm:
            raise AssertionError(
                f"{path}: {zw} zeros in the tuned weight, {zm} in its mask, "
                f"{bad} pruned slots nonzero")
    zeros = sum(c[2] for c in counts)
    size = sum(c[4] for c in counts)
    if abs(zeros / size - sparsity) > 5e-3:
        raise AssertionError(f"mask sparsity {zeros / size} is not "
                             f"{sparsity}")

    payload = res["payload"] or {}
    compile_s = {k: v for k, v in (payload.get("walk_phases") or {}).items()
                 if k.endswith("_compile")}
    say(f"walk {mesh[0]}x{mesh[1]}: ok in {wall:.1f}s wall; blocks "
        + ", ".join(f"{r.loss_before:.4e}->{r.loss_after:.4e}"
                    for r in reports))
    say(f"walk {mesh[0]}x{mesh[1]}: perplexity "
        + ", ".join(f"{k} {v:.2f}" for k, v in res["perplexity"].items())
        + f"; {len(counts)} prunable weights zero exactly where masked "
        f"({zeros / size:.4f} of {size})")
    say(f"walk {mesh[0]}x{mesh[1]}: phase seconds "
        + json.dumps({k: round(v, 3) for k, v in res["phases"].items()})
        + "; walk build seconds per phase "
        + json.dumps({k: v and round(v, 3) for k, v in compile_s.items()}))
    return [r.loss_after for r in reports], res["tuned"]


def serve(cfg, *, requests=8, slots=4, prompt=128, new=32, max_len=256,
          sparse=0.5, out_dir=OUT_DIR):
    """Phase 2: ``serve.main`` at ``cfg`` on wanda-pruned weights; checks
    every request's ids, then prefill + one decode step against the full
    forward pass."""
    import jax
    import jax.numpy as jnp

    from repro.launch import serve as serve_mod

    argv = ["--arch", ARCH, "--sparse", str(sparse),
            "--requests", str(requests), "--slots", str(slots),
            "--prompt-len", str(prompt), "--max-new", str(new),
            "--max-len", str(max_len),
            "--bench-out", os.path.join(out_dir, "BENCH_serve.json")]
    t0 = time.perf_counter()
    res = serve_mod.main(argv, cfg=cfg)
    wall = time.perf_counter() - t0

    results = res["results"]
    if sorted(results) != list(range(requests)):
        raise AssertionError(f"served {sorted(results)} of {requests}")
    for uid, ids in results.items():
        if len(ids) != new or not all(0 <= t < cfg.vocab_size for t in ids):
            raise AssertionError(f"request {uid}: {len(ids)} ids, "
                                 f"range [{min(ids)}, {max(ids)}]")

    model, params = res["model"], res["params"]
    toks = jnp.asarray(np.random.default_rng(1).integers(
        0, cfg.vocab_size, size=(2, prompt)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        full = jax.jit(model.forward)(params, {"tokens": toks})
        state = model.init_serve_state(2, prompt + 1)
        lp, state = jax.jit(model.prefill)(
            params, {"tokens": toks[:, :-1]}, state)
        ld, _ = jax.jit(model.decode_step)(params, toks[:, -1:], state)
    scale = float(jnp.max(jnp.abs(full[:, -2:])))
    errs = {"prefill": float(jnp.max(jnp.abs(lp[:, -1] - full[:, -2]))),
            "decode": float(jnp.max(jnp.abs(ld[:, -1] - full[:, -1])))}
    for name, err in errs.items():
        if not err <= DECODE_RTOL * scale:
            raise AssertionError(
                f"{name} logits off the forward pass by {err} "
                f"(limit {DECODE_RTOL} x {scale})")

    from repro.obs import trace as OT

    forest = (res["payload"] or {}).get("trace", [])
    build_s = {f"serve/{name}": OT.totals(forest, f"serve/{name}")[1]
               for name in ("admit", "step")}
    say(f"serve: ok in {wall:.1f}s wall; {requests} requests x {new} ids "
        f"in the vocabulary; serving loop {res['seconds']:.2f}s; build "
        f"seconds {json.dumps({k: round(v, 3) for k, v in build_s.items()})}")
    say(f"serve: prefill/decode against forward at highest precision, max "
        f"abs error {errs['prefill']:.3e}/{errs['decode']:.3e}, logit scale "
        f"{scale:.3e}, limit {DECODE_RTOL} x scale")


def check_mesh_placement(tuned, devices) -> None:
    """The mesh walk's arrays sit on every device of the host."""
    import jax

    spread = {len(x.sharding.device_set) for x in jax.tree.leaves(tuned)}
    peaks = [peak_bytes(d) for d in devices]
    say(f"mesh placement: tuned leaves span {sorted(spread)} devices; "
        f"peak bytes per device {peaks}")
    if max(spread) != len(devices):
        raise AssertionError(f"no tuned leaf spans all {len(devices)} "
                             "devices")
    if min(peaks) < (1 << 20):
        raise AssertionError(f"a device held under 1 MiB: {peaks}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: the mesh walk against one device, nothing else")
    args = ap.parse_args(argv)

    import jax

    if jax.default_backend() != "tpu":
        raise SystemExit(f"chip_smoke.py needs a TPU; JAX found "
                         f"{jax.default_backend()!r}")
    devices = jax.devices()
    if len(devices) < args.chips:
        raise SystemExit(f"--chips {args.chips} but JAX sees "
                         f"{len(devices)} device(s)")

    from repro.configs import get_config
    from repro.launch.compile_cache import use_compile_cache

    cache = use_compile_cache()
    cfg = get_config(ARCH).replace(**CUT)
    dev = devices[0]
    say(f"smoke run (not a benchmark) on {len(devices)} x {dev.device_kind}; "
        f"compile cache {cache}")
    say(f"config {ARCH} cut to {CUT} (published: 40 layers, vocabulary "
        f"151936): {CUT_WHY}; widths as published: hidden {cfg.d_model}, "
        f"FFN {cfg.d_ff}, {cfg.num_heads} heads of "
        f"{cfg.resolved_head_dim}, {cfg.num_kv_heads} KV heads")

    if args.chips == 4:
        mesh_loss, tuned = walk(cfg, mesh=(2, 2))
        check_mesh_placement(tuned, devices[:4])
        del tuned
        one_loss, _ = walk(cfg, mesh=(1, 1))
        diffs = [abs(a - b) / abs(b) for a, b in zip(mesh_loss, one_loss)]
        say(f"mesh vs one device: per-block loss_after relative "
            f"difference {[f'{d:.3e}' for d in diffs]}, limit {MESH_RTOL}")
        if not max(diffs) <= MESH_RTOL:
            raise AssertionError("the 2x2 mesh walk disagrees with the "
                                 "one-device walk")
    else:
        walk(cfg)
        say(f"walk: peak bytes in use {peak_bytes(dev)}")
        serve(cfg)
        say(f"serve: peak bytes in use (process) {peak_bytes(dev)}")

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
