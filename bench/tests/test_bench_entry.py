"""The command's refusals: no TPU, no program beside the benchmark, an
unknown cell, an unknown chip. Each exits non-zero and prints no result."""
import os
import shutil
import subprocess
import sys

import bench_tiny as T
import pytest

from harness import device


def run(root, workload="qwen4b-walk"):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(root, "bench", "run.py"), "--workload", workload,
         "--seed", str(2**31 + 7), "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300, env=env, cwd=root)


def no_result(p):
    return not any(line.lstrip().startswith("{") for line in p.stdout.splitlines())


def test_no_tpu_exits_without_result():
    p = run(T.ROOT)
    assert p.returncode != 0 and no_result(p)
    assert "needs a TPU" in p.stderr


def test_benchmark_alone_exits_without_result(tmp_path):
    shutil.copy(os.path.join(T.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(T.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = run(str(tmp_path))
    assert p.returncode != 0 and no_result(p)


def test_unknown_workload_exits_without_result():
    p = run(T.ROOT, "no-such-cell")
    assert p.returncode == 2 and no_result(p)


def test_peaks_refuse_an_unknown_chip():
    assert device.peaks("TPU v5 lite")["flops"] == 197e12
    with pytest.raises(device.NoChip):
        device.peaks("TPU v99")
