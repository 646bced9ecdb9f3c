"""The plain references agree with the program's models (repro.models) on
the benchmark's seeded weights, at the tiny sizes, on the CPU: each block,
and the whole forward pass."""
import bench_tiny as T
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import layout, program

RTOL = 1e-4  # of the output's scale: float32 against float32, summation order only


@pytest.mark.parametrize("conf", [T.TINY_QWEN, T.TINY_MAMBA, T.TINY_MOE],
                         ids=["qwen1_5", "mamba2", "moe"])
def test_reference_matches_model(conf):
    ref = T.reference(conf)
    model = program.build(program.model_config(conf))
    params = jax.jit(lambda k: ref.init(k, conf))(jax.random.PRNGKey(3))
    toks = jnp.asarray(np.random.default_rng(0).integers(0, conf["vocab_size"], (2, 32)),
                       jnp.int32)
    h = ref.embed(params, toks)
    pos = jnp.arange(32)[None, :]
    for i in range(layout.count(ref, params)):
        bp = layout.block(ref, params, i)
        want = ref.block(bp, h, conf)
        got = model.apply_block(None, i, bp, h, pos)
        assert float(jnp.max(jnp.abs(got - want))) <= RTOL * float(jnp.max(jnp.abs(want)))
        h = want
    want = ref.forward(params, toks, conf)
    got = model.forward(params, {"tokens": toks})[..., : conf["vocab_size"]]
    assert got.shape == want.shape
    assert float(jnp.max(jnp.abs(got - want))) <= RTOL * float(jnp.max(jnp.abs(want)))
