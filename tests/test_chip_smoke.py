"""chip_smoke.py and the entry behaviour it relies on, on the CPU: no TPU
means no result, the compile cache lands where it should, the device is
never guessed, and the smoke's phases pass at a tiny size."""
from __future__ import annotations

import importlib.util
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _env(**extra):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(PYTHONPATH=os.path.join(ROOT, "src"), JAX_PLATFORMS="cpu",
               **extra)
    return env


def test_chip_smoke_refuses_without_a_tpu():
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT,
                          env=_env(), capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "needs a TPU" in proc.stderr


CACHE_PROBE = r"""
import os
import jax, jax.numpy as jnp
from repro.launch.compile_cache import use_compile_cache
path = use_compile_cache()
assert jax.config.jax_compilation_cache_dir == path, path
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.jit(lambda x: x * 3 + 1)(jnp.ones(3)).block_until_ready()
print(path)
"""


@pytest.mark.parametrize("env_set", [True, False])
def test_compile_cache_location(tmp_path, env_set):
    extra = {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)} if env_set else {}
    proc = subprocess.run([sys.executable, "-c", CACHE_PROBE],
                          env=_env(**extra), capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    path = proc.stdout.strip().splitlines()[-1]
    if env_set:
        assert path == str(tmp_path)
        assert os.listdir(tmp_path), "nothing was cached in the set dir"
    else:
        assert path == os.path.join(ROOT, ".jax_cache")


@pytest.mark.parametrize("known", [False, True])
def test_roofline_only_for_known_device_kinds(monkeypatch, known):
    from repro.launch import rooflines
    from repro.obs.profile import record_kernel
    from repro.obs.run import start_run

    kind = jax.devices()[0].device_kind
    assert kind not in rooflines.PEAKS  # the CPU is not in the table
    if known:
        monkeypatch.setitem(rooflines.PEAKS, kind,
                            {"flops": 1e12, "hbm_bw": 1e11, "ici_bw": 1e10})
    run = start_run("roofline_test", console=False)
    record_kernel("kernels/t", 2e12, 1e9, lambda x: x + 1, jnp.ones(4))
    metrics = run.finish()["metrics"]
    assert metrics["kernels/t/calls"]["value"] == 1
    if known:
        assert metrics["kernels/t/roofline_ideal_s"]["last"] == pytest.approx(2.0)
    else:
        assert "kernels/t/roofline_ideal_s" not in metrics


def test_v5e_peaks_are_tabled():
    from repro.launch.rooflines import PEAKS

    assert PEAKS["TPU v5 lite"] == {"flops": 197e12, "hbm_bw": 819e9,
                                    "ici_bw": 50e9}


def test_profiled_compile_failure_raises():
    from repro.obs.profile import profiled
    from repro.obs.run import start_run

    run = start_run("compile_fail_test", console=False)
    try:
        with pytest.raises(TypeError):
            profiled(jax.jit(lambda x: x.reshape(7)), "t/bad")(jnp.ones(3))
    finally:
        run.finish()


def test_manifest_names_the_device():
    from repro.obs.run import start_run

    run = start_run("manifest_test", console=False)
    manifest = run.finish()["manifest"]
    dev = jax.devices()[0]
    assert (manifest["platform"], manifest["device_kind"],
            manifest["device_count"]) == (dev.platform, dev.device_kind,
                                          jax.device_count())


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_smoke_phases_pass_at_tiny_size(smoke, tmp_path, capsys):
    """The smoke's walk and serving phases, with all their checks, on
    tiny_dense: the rehearsal of the chip run."""
    from repro.configs import get_config

    cfg = get_config("tiny_dense")
    losses, _ = smoke.walk(cfg, seq=64, calib=16, out_dir=str(tmp_path))
    assert len(losses) == cfg.num_layers
    smoke.serve(cfg, prompt=32, new=8, max_len=64, out_dir=str(tmp_path))
    text = capsys.readouterr().out
    assert "walk 1x1: ok" in text and "serve: ok" in text
