"""N:M sparse matmul Pallas TPU kernel: out = x @ decompress(vals, idx).

TPU has no sparse tensor cores, so the honest N:M win on TPU is **HBM
bandwidth and footprint** (DESIGN.md §3): a 2:4 weight stores N/M = ½ the
values plus int8 group offsets (2-bit packable), i.e. ~0.56× the bytes of
the dense bf16 weight. This kernel streams the *compressed* representation
HBM→VMEM, decompresses each (bk, bn) weight tile in VMEM with compares
and 0/1 expansion matmuls (no scatter or gather — TPU-vector friendly),
and feeds the dense tile straight to the MXU.

Layout (produced by sparsity/sparse_params.nm_compress):
    vals (K//m·n, N)   kept values, group-major along K
    idx  (K//m·n, N)   int8 offset of each kept value inside its M-group

Grid: (M/bm, N/bn, K/bk) with the f32 accumulator in VMEM scratch across
the K sweep. The compressed K-tile has bk//m·n rows — contiguous, since
groups follow K order.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.validation import plan_nm_spmm


def _kernel(x_ref, v_ref, i_ref, o_ref, acc_ref, *, n: int, m: int, k_steps: int):
    @pl.when(pl.program_id(2) == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    vals = v_ref[...]                      # (G*n, bn)
    idx = i_ref[...].astype(jnp.int32)     # (G*n, bn)
    bkc = vals.shape[0]
    bk = bkc // n * m

    # VMEM decompress with 2-D ops only (Mosaic lowers no 3-D gather):
    # dense row g*m+o = sum over the group's n kept rows whose offset is o,
    # i.e. dense = sum_o Q_o @ where(idx == o, vals, 0) with the 0/1
    # expansion Q_o[g*m+o, g*n+s] = 1. HIGHEST keeps the f32 values exact.
    row = jax.lax.broadcasted_iota(jnp.int32, (bk, bkc), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (bk, bkc), 1)
    same_group = (row // m) == (col // n)
    w_tile = jnp.zeros((bk, vals.shape[1]), jnp.float32)
    for o in range(m):  # m is tiny (2..8): unrolled
        q = (same_group & (row % m == o)).astype(vals.dtype)
        z = jnp.where(idx == o, vals, jnp.zeros_like(vals))
        w_tile = w_tile + jnp.dot(q, z, preferred_element_type=jnp.float32,
                                  precision=jax.lax.Precision.HIGHEST)
    w_tile = w_tile.astype(vals.dtype)     # (bk, bn)

    acc_ref[...] += jnp.dot(x_ref[...], w_tile, preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("n", "m", "bm", "bk", "bn", "interpret")
)
def nm_spmm(
    x: jax.Array,     # (M, K)
    vals: jax.Array,  # (K//m*n, N)
    idx: jax.Array,   # (K//m*n, N) int8
    *,
    n: int,
    m: int,
    bm: int = 128,
    bk: int = 128,
    bn: int = 128,
    interpret: bool = False,
) -> jax.Array:
    M, K = x.shape
    KC, N = vals.shape
    if KC * m != K * n:
        raise ValueError(
            f"nm_spmm: compressed rows {KC} inconsistent with K={K} under "
            f"{n}:{m} (want K//m*n = {K // m * n})"
        )
    # validates group alignment + tile divisibility (after clamping) and is
    # the exact plan repro.analysis checks statically
    plan = plan_nm_spmm(
        M, K, N, n=n, m=m, bm=bm, bk=bk, bn=bn,
        x_dtype=x.dtype, v_dtype=vals.dtype,
    )
    k_steps = plan.grid[2]
    xb, vb, ib = plan.inputs
    (ob,) = plan.outputs

    return pl.pallas_call(
        functools.partial(_kernel, n=n, m=m, k_steps=k_steps),
        grid=plan.grid,
        in_specs=[
            pl.BlockSpec(xb.shape, xb.index_map),
            pl.BlockSpec(vb.shape, vb.index_map),
            pl.BlockSpec(ib.shape, ib.index_map),
        ],
        out_specs=pl.BlockSpec(ob.shape, ob.index_map),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM(ob.shape, jnp.float32)],
        interpret=interpret,
    )(x, vals, idx.astype(jnp.int8))
