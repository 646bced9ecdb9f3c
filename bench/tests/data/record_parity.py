#!/usr/bin/env python3
"""Records ``parity.json``: what the harness read at commit 38d981f, the
last before the block layout and the work counts moved into the
configurations' references. ``test_bench_parity.py`` holds the harness
to it.

    git archive 38d981f | tar -x -C <dir>
    JAX_PLATFORMS=cpu python3 bench/tests/data/record_parity.py <dir>/bench <out.json>

With a third argument, ``cells``, it writes the cells' part alone, which
runs on any later commit (the test does so).

For ``tiny-walk`` and ``tiny-serve`` at ``bench_tiny.SEED``: a digest of
the Wanda masks and every number the check reads (with the walk's
per-block notes), from a run whose window has a fixed length: the
driver's clock advances one second a read, so the walk's window makes
one ``finetune`` call and serving's serves six requests. For four
configurations: every count of ``flops.py`` at that commit, which took a
configuration and gave every block the same counts; here they are
written out per block.

XLA's CPU thread pool is as large as the process's CPU affinity, and the
walk's rounding-level gaps move with it, so the script runs on one core.
"""
import hashlib
import json
import os
import sys

SEED_CELLS = {"tiny-walk": 1, "tiny-serve": 6}  # cell -> window seconds (clock reads)
COUNT_CONFIGS = ("tiny_qwen", "tiny_mamba", "qwen1_5_4b-4l", "mamba2_130m")
# the sizes the counts are taken at: (seq, tokens, epochs) of a walk block,
# (prompt, out) of a request, attended positions of a decode
WALKS = [(32, 512, 3), (1024, 65536, 10), (1024, 65536, 4)]
REQUESTS = [(8, 5), (128, 16), (1024, 256)]
ATTENDED = [42, 100000]
RENAMED = {"blocks": "per_block"}


class Clock:
    """A ``time`` stand-in whose ``perf_counter`` reads 0, 1, 2, ..."""

    def __init__(self):
        self.now = -1.0

    def perf_counter(self):
        self.now += 1.0
        return self.now


def digest(tree) -> str:
    """sha256 over every leaf's path, shape, dtype and bytes, in path order."""
    import jax
    import numpy as np

    h = hashlib.sha256()
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    for path, leaf in sorted(leaves, key=lambda kv: jax.tree_util.keystr(kv[0])):
        a = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(path)} {a.shape} {a.dtype}\n".encode())
        h.update(a.tobytes())
    return h.hexdigest()


def drive(root: str, name: str):
    """One run of a tiny cell with a window of fixed length: the masks'
    digest, every number the check reads, and the check's notes."""
    import jax

    import bench_tiny as T
    from harness import cli, traffic, wanda

    cell = T.cell(root, name)
    mod = cell.driver()
    mod.time = Clock()
    ctx = cli.Context(cell, T.SEED, jax.devices(), None)
    with ctx.computing():
        drv = mod.Driver(ctx)
        drv.setup()
        drv.window(SEED_CELLS[name])
        drv.free()
    if hasattr(drv, "masks"):
        masks = drv.masks
    else:  # serving keeps only the pruned weights: the masks of its own call
        conf, tr, ref = ctx.conf, ctx.traffic, ctx.ref
        params = jax.jit(lambda k: ref.init(k, conf))(traffic.jax_key(T.SEED))
        masks, _ = wanda.masks(ref, conf, params,
                               traffic.mask_calibration(tr, conf["vocab_size"], T.SEED),
                               tr["prune"]["sparsity"], tr["prune"]["microbatch"])
    check = drv.check()
    # the walk's per-block notes were keyed "blocks" at 38d981f
    notes = {RENAMED.get(k, k): v for k, v in getattr(drv, "notes", {}).items()}
    return {"masks": digest(masks), "check": check, "notes": json.loads(json.dumps(notes))}


def configurations(bench: str):
    import bench_tiny as T
    from harness import spec as S

    return {"tiny_qwen": T.TINY_QWEN, "tiny_mamba": T.TINY_MAMBA,
            "qwen1_5_4b-4l": S.load_json(os.path.join(bench, "configs", "qwen1_5_4b-4l.json")),
            "mamba2_130m": S.load_json(os.path.join(bench, "configs", "mamba2_130m.json"))}


def counts_by_configuration(flops, conf):
    """The per-configuration counts of ``flops.py`` at 38d981f, per block."""
    def attempt(f, *a):
        try:
            return f(conf, *a)
        except ValueError:  # K/V bytes of a model without attention
            return None

    L = flops.layers(conf)
    return {
        "blocks": L,
        "block_matmul_params": [flops.block_matmul_params(conf)] * L,
        "block_params": [flops.block_params(conf)] * L,
        "block_fwd_flops_per_token": {
            str(seq): [flops.block_fwd_flops_per_token(conf, seq)] * L for seq in (32, 1024)},
        "walk_block_flops": {
            f"{s} {t} {e}": [flops.walk_block_flops(conf, s, t, e)] * L for s, t, e in WALKS},
        "serve_request_flops": {
            f"{p} {o}": flops.serve_request_flops(conf, p, o) for p, o in REQUESTS},
        "decode_weight_bytes": flops.decode_weight_bytes(conf),
        "decode_kv_bytes": {str(a): attempt(flops.decode_kv_bytes, a) for a in ATTENDED},
        "decode_request_bytes": {
            f"{p} {o}": attempt(flops.decode_request_bytes, p, o) for p, o in REQUESTS},
    }


def main(bench: str, out: str, what: str = "all"):
    os.sched_setaffinity(0, {min(os.sched_getaffinity(0))})
    bench = os.path.abspath(bench)
    sys.path[:0] = [bench, os.path.join(bench, "tests")]
    import tempfile

    import bench_tiny as T
    from harness import flops

    with tempfile.TemporaryDirectory() as tmp:
        root = T.make_root(tmp)
        cells = {name: drive(root, name) for name in SEED_CELLS}
    record = {"seed": T.SEED, "cells": cells}
    if what == "all":
        confs = configurations(bench)
        record["counts"] = {n: counts_by_configuration(flops, confs[n]) for n in COUNT_CONFIGS}
    with open(out, "w") as f:
        json.dump(record, f, indent=1, sort_keys=True)
        f.write("\n")


if __name__ == "__main__":
    main(*sys.argv[1:4])
