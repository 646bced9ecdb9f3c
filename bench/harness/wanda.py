"""The benchmark's own Wanda masks (Sun et al. 2023), made in set-up.

score_ij = |W_ij| * ||X_i||_2 over the calibration tokens, where i is an
input unit of the linear layer; each output unit keeps the top
(1 - sparsity) of its inputs. The activations come from the pruned
stream: block l is scored on the output of the already-pruned blocks
before it, as the official implementation does. Only the linear layers
the reference lists in ``PRUNABLE`` are pruned; a path that a block does
not have is not in that block (a dense block has no experts).

An entry marked ``EXPERTS`` is a leaf whose leading axis indexes
experts, and its tap carries that axis too: each expert's inputs, zero
where a token is not routed to it. Each expert then keeps the top
(1 - sparsity) of its inputs per output unit, scored on its own tokens.
An expert that no calibration token reaches scores every input 0 and
keeps every weight.

The masks are made here, not by the program's pruner, so that the plain
reference and the program start from inputs that neither of them made.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from harness import layout
from harness.spec import SpecError

EXPERTS = "experts"  # third element of a PRUNABLE entry: leading axis = experts
TOKENS = (-3, -2)    # a tap's token axes (batch, sequence), before its last


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


def expert_mask(w, col_sq, lead: int, sparsity: float):
    """``leaf_mask`` of each expert: w (E, ...), col_sq (E, inputs)."""
    return jax.vmap(lambda we, sq: leaf_mask(we, sq, lead, sparsity))(w, col_sq)


def _has(tree, path: str) -> bool:
    for part in path.split("/"):
        if not isinstance(tree, dict) or part not in tree:
            return False
        tree = tree[part]
    return True


def masks(ref, conf: Dict[str, Any], params, tokens: np.ndarray,
          sparsity: float, microbatch: int):
    """(masks, pruned params) in the program's layout: 0/1 float32 on the
    pruned leaves, ones on the other block leaves, scalar 1 elsewhere."""
    hp = jax.lax.Precision.DEFAULT  # ranking only: rounding moves no mask much

    @jax.jit
    def col_sq(bp, h):
        def one(acc, hm):
            _, taps = ref.block(bp, hm, conf, taps=True, precision=hp)
            return {k: acc[k] + jnp.sum(jnp.square(v), axis=TOKENS)
                    for k, v in taps.items()}, None

        hm = h.reshape(-1, microbatch, *h.shape[1:])
        _, taps = jax.eval_shape(lambda: ref.block(bp, hm[0], conf, taps=True))
        acc0 = {k: jnp.zeros(v.shape[:-3] + v.shape[-1:], jnp.float32)
                for k, v in taps.items()}
        return jax.lax.scan(one, acc0, hm)[0]

    @jax.jit
    def advance(bp, h):
        hm = h.reshape(-1, microbatch, *h.shape[1:])
        out = jax.lax.map(lambda x: ref.block(bp, x, conf, precision=hp), hm)
        return out.reshape(h.shape)

    leaf_fn = jax.jit(leaf_mask, static_argnums=(2, 3))
    expert_fn = jax.jit(expert_mask, static_argnums=(2, 3))
    h = jax.jit(ref.embed)(params, jnp.asarray(tokens))
    per_block, used = [], set()
    for i in range(layout.count(ref, params)):
        bp = layout.block(ref, params, i)
        sq = col_sq(bp, h)
        mb = jax.tree.map(jnp.ones_like, bp)
        for path, (tap, lead, *axis) in ref.PRUNABLE.items():
            if _has(bp, path):
                fn = expert_fn if axis == [EXPERTS] else leaf_fn
                mb = _set(mb, path, fn(_leaf(bp, path), sq[tap], lead, sparsity))
                used.add(path)
        per_block.append(mb)
        h = advance(jax.tree.map(jnp.multiply, bp, mb), h)
    if set(ref.PRUNABLE) - used:
        raise SpecError(f"PRUNABLE names leaves no block has: "
                        f"{sorted(set(ref.PRUNABLE) - used)}")
    m = layout.stacked(ref, params, per_block,
                       lambda v: jax.tree.map(lambda _: jnp.ones((), jnp.float32), v))
    pruned = jax.tree.map(jnp.multiply, params, m)
    return m, pruned
