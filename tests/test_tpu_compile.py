"""Ahead-of-time compiles for a described TPU v5e chip, at real widths.

Nothing runs: the TPU compiler, which is installed with JAX, compiles for
the chip of a ``v5e:2x2`` topology description, and refuses what the chip
would refuse (misaligned tiles, too much VMEM, a program that does not fit
16 GiB). The topology and everything built from it live in module-scoped
fixtures, never at import, so every test worker collects the same tests.
"""
from __future__ import annotations

import importlib.util
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

HBM_BYTES = 16 * 1024 ** 3
M = 8192  # 8 x 1024 calibration tokens, one EBFT microbatch


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a described chip's executables cannot be read back without one, so
    # keep them out of the persistent cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no description
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("K,N", [(2560, 7680), (2560, 2560), (2560, 6912),
                                 (6912, 2560)])
def test_masked_matmul_compiles(one_chip, K, N):
    from repro.kernels.masked_matmul.masked_matmul import masked_matmul

    _kernel_compile(masked_matmul,
                    _spec((M, K), jnp.float32, one_chip),
                    _spec((K, N), jnp.float32, one_chip),
                    _spec((K, N), jnp.int8, one_chip))


def test_nm_spmm_2_4_compiles(one_chip):
    from repro.kernels.nm_spmm.nm_spmm import nm_spmm

    K, N, n, m = 2560, 6912, 2, 4
    _kernel_compile(lambda x, v, i: nm_spmm(x, v, i, n=n, m=m),
                    _spec((M, K), jnp.float32, one_chip),
                    _spec((K // m * n, N), jnp.float32, one_chip),
                    _spec((K // m * n, N), jnp.int8, one_chip))


def test_flash_attention_compiles(one_chip):
    from repro.kernels.flash_attention.flash_attention import flash_attention

    qkv = _spec((8 * 20, 1024, 128), jnp.float32, one_chip)
    _kernel_compile(flash_attention, qkv, qkv, qkv)


def _chip_smoke():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(root, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_ebft_tune_step_fits_one_chip(one_chip):
    """The fused, donated tune step for one block of the smoke run's cut
    config (qwen1_5_4b widths) compiles and fits the chip."""
    from repro.configs import get_config
    from repro.core import ebft
    from repro.core import reconstruction as R
    from repro.models.model import build

    smoke = _chip_smoke()
    cfg = get_config(smoke.ARCH).replace(**smoke.CUT)
    model = build(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    block = jax.eval_shape(lambda p: model.get_block(p, 0), params)
    seg = R.execution_plan(model)[0]
    n_mb, mb, seq = 8, 8, 1024  # 64 calibration samples of 1024 tokens
    batch = {"tokens": jax.ShapeDtypeStruct((n_mb, mb, seq), jnp.int32)}
    h, pos = jax.eval_shape(
        lambda p, b: jax.lax.map(lambda x: seg.h0(p, x), b), params, batch)
    aux = jax.eval_shape(
        lambda p, b: jax.lax.map(lambda x: seg.aux(p, x), b), params, batch)

    def place(tree):
        return jax.tree.map(
            lambda s: _spec(s.shape, s.dtype, one_chip), tree)

    fused = ebft._make_tune_step(model, 0, ebft.EBFTConfig(epochs=2))[3]
    compiled = fused.lower(place(block), place(block), place(h), place(h),
                           place(pos), place(aux)).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < HBM_BYTES, mem
