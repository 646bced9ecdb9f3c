"""The benchmark's own Wanda masks (Sun et al. 2023), made in set-up.

score_ij = |W_ij| * ||X_i||_2 over the calibration tokens, where i is an
input unit of the linear layer; each output unit keeps the top
(1 - sparsity) of its inputs. The activations come from the pruned
stream: block l is scored on the output of the already-pruned blocks
before it, as the official implementation does. Only the linear layers
the reference lists in ``PRUNABLE`` are pruned.

The masks are made here, not by the program's pruner, so that the plain
reference and the program start from inputs that neither of them made.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np


def _leaf(tree, path: str):
    for part in path.split("/"):
        tree = tree[part]
    return tree


def _set(tree, path: str, value):
    head, _, rest = path.partition("/")
    return {**tree, head: value if not rest else _set(tree[head], rest, value)}


def leaf_mask(w, col_sq, lead: int, sparsity: float):
    """w: one block's leaf; col_sq: sum of squares of its inputs over the
    tokens, flattened over the ``lead`` input axes."""
    R = int(np.prod(w.shape[:lead]))
    mat = jnp.abs(w.reshape(R, -1)) * jnp.sqrt(col_sq)[:, None]
    keep = max(1, int(round(R * (1.0 - sparsity))))
    kth = jax.lax.top_k(mat.T, keep)[0][:, -1]          # per output unit
    return (mat >= kth[None, :]).astype(jnp.float32).reshape(w.shape)


def masks(ref, conf: Dict[str, Any], params, tokens: np.ndarray,
          sparsity: float, microbatch: int):
    """(masks, pruned params) in the program's layout: 0/1 float32 on the
    pruned leaves, ones on the other block leaves, scalar 1 elsewhere."""
    L = jax.tree.leaves(params["blocks"])[0].shape[0]
    hp = jax.lax.Precision.DEFAULT  # ranking only: rounding moves no mask much

    @jax.jit
    def col_sq(bp, h):
        def one(acc, hm):
            _, taps = ref.block(bp, hm, conf, taps=True, precision=hp)
            return {k: acc[k] + jnp.sum(jnp.square(v), axis=(0, 1))
                    for k, v in taps.items()}, None

        hm = h.reshape(-1, microbatch, *h.shape[1:])
        _, taps = jax.eval_shape(lambda: ref.block(bp, hm[0], conf, taps=True))
        acc0 = {k: jnp.zeros(v.shape[-1:], jnp.float32) for k, v in taps.items()}
        return jax.lax.scan(one, acc0, hm)[0]

    @jax.jit
    def advance(bp, h):
        hm = h.reshape(-1, microbatch, *h.shape[1:])
        out = jax.lax.map(lambda x: ref.block(bp, x, conf, precision=hp), hm)
        return out.reshape(h.shape)

    mask_fn = jax.jit(leaf_mask, static_argnums=(2, 3))
    h = jax.jit(ref.embed)(params, jnp.asarray(tokens))
    per_layer = []
    for i in range(L):
        bp = ref.layer(params, i)
        sq = col_sq(bp, h)
        mb = jax.tree.map(jnp.ones_like, bp)
        for path, (tap, lead) in ref.PRUNABLE.items():
            mb = _set(mb, path, mask_fn(_leaf(bp, path), sq[tap], lead, sparsity))
        per_layer.append(mb)
        h = advance(jax.tree.map(jnp.multiply, bp, mb), h)
    block_masks = jax.tree.map(lambda *xs: jnp.stack(xs), *per_layer)
    m = {k: (block_masks if k == "blocks" else
             jax.tree.map(lambda _: jnp.ones((), jnp.float32), v))
         for k, v in params.items()}
    pruned = jax.tree.map(jnp.multiply, params, m)
    return m, pruned
