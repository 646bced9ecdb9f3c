"""What the harness reads of the existing configurations is what it read
at commit 38d981f, before the block layout and the work counts moved
into the configurations' references (``data/parity.json``, written by
``data/record_parity.py`` there): the Wanda masks and every number the
check reads of tiny-walk and tiny-serve at ``bench_tiny.SEED``, bit for
bit, and every operation and byte count of four configurations."""
import json
import os
import subprocess
import sys

import bench_tiny as T
import pytest

from harness import flops
from harness import spec as S

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORD = S.load_json(os.path.join(DATA, "parity.json"))
R = S.load_module(os.path.join(DATA, "record_parity.py"), "record_parity")


@pytest.fixture(scope="module")
def cells(tmp_path_factory):
    """The cells' part, recorded now by the same script, on one core."""
    out = tmp_path_factory.mktemp("parity") / "cells.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.path.join(T.ROOT, "src"))
    subprocess.run([sys.executable, os.path.join(DATA, "record_parity.py"), T.BENCH,
                    str(out), "cells"], check=True, env=env, cwd=T.ROOT, timeout=900)
    with open(out) as f:
        return json.load(f)


@pytest.mark.parametrize("part", ["masks", "check", "notes"])
@pytest.mark.parametrize("name", sorted(R.SEED_CELLS))
def test_cell_reads_as_recorded(cells, name, part):
    assert cells["seed"] == RECORD["seed"]
    assert cells["cells"][name][part] == RECORD["cells"][name][part]


def counts(ref, conf):
    """``record_parity.counts_by_configuration``'s numbers, from the
    per-block counts of the configuration's reference."""
    def attempt(f, *a):
        try:
            return f(ref, conf, *a)
        except ValueError:  # K/V bytes of a model without attention
            return None

    blocks = range(ref.num_blocks(conf))
    return {
        "blocks": len(blocks),
        "block_matmul_params": [ref.block_matmul_params(conf, i) for i in blocks],
        "block_params": [ref.block_params(conf, i) for i in blocks],
        "block_fwd_flops_per_token": {
            str(seq): [ref.block_fwd_flops_per_token(conf, i, seq) for i in blocks]
            for seq in (32, 1024)},
        "walk_block_flops": {
            f"{s} {t} {e}": [flops.walk_block_flops(ref, conf, i, s, t, e) for i in blocks]
            for s, t, e in R.WALKS},
        "serve_request_flops": {
            f"{p} {o}": flops.serve_request_flops(ref, conf, p, o) for p, o in R.REQUESTS},
        "decode_weight_bytes": flops.decode_weight_bytes(ref, conf),
        "decode_kv_bytes": {str(a): attempt(flops.decode_kv_bytes, a) for a in R.ATTENDED},
        "decode_request_bytes": {
            f"{p} {o}": attempt(flops.decode_request_bytes, p, o) for p, o in R.REQUESTS},
    }


@pytest.mark.parametrize("name", R.COUNT_CONFIGS)
def test_counts_as_recorded(name):
    conf = R.configurations(T.BENCH)[name]
    assert counts(T.reference(conf), conf) == RECORD["counts"][name]
