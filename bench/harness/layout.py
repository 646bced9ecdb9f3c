"""Where a model's blocks lie in the program's parameter tree.

A configuration's reference module names, in ``BLOCK_KEYS``, the
top-level keys of the tree that hold its blocks, in the order the
program visits them. Each such key holds a stack of blocks of one kind,
every leaf with the block on its leading axis. Block ``i`` is found by
offsets over the keys, as the program's ``Model.get_block`` finds it.
Every other top-level key (embedding, final norm, head) lies outside the
blocks.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp


def sizes(ref, tree) -> List[int]:
    """Blocks under each of ``ref.BLOCK_KEYS``, in order."""
    return [jax.tree.leaves(tree[k])[0].shape[0] for k in ref.BLOCK_KEYS]


def count(ref, tree) -> int:
    return sum(sizes(ref, tree))


def locate(ref, tree, i: int) -> Tuple[str, int]:
    """(key, index under that key) of block ``i``."""
    for key, n in zip(ref.BLOCK_KEYS, sizes(ref, tree)):
        if i < n:
            return key, i
        i -= n
    raise IndexError(f"no block {i}: the tree holds {count(ref, tree)}")


def block(ref, tree, i: int):
    """Block ``i`` of ``tree``, its leaves without the stacking axis."""
    key, j = locate(ref, tree, i)
    return jax.tree.map(lambda a: a[j], tree[key])


def stacked(ref, like: Dict[str, Any], per_block: Sequence[Any],
            rest: Callable[[Any], Any]) -> Dict[str, Any]:
    """A tree in the program's layout, with the top-level keys of
    ``like``: the per-block trees stacked under their block keys, and
    ``rest(subtree)`` for every other key."""
    out, start = {}, 0
    for key, n in zip(ref.BLOCK_KEYS, sizes(ref, like)):
        out[key] = jax.tree.map(lambda *xs: jnp.stack(xs), *per_block[start:start + n])
        start += n
    return {k: out[k] if k in out else rest(v) for k, v in like.items()}


def map_blocks(ref, f: Callable[[Any], Any], tree: Dict[str, Any]) -> Dict[str, Any]:
    """``tree`` with ``f`` applied to every leaf of its blocks."""
    return {k: jax.tree.map(f, v) if k in ref.BLOCK_KEYS else v for k, v in tree.items()}
