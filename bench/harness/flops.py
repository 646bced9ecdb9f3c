"""Operations and bytes the algorithm requires, composed from per-block counts.

These are the yardstick's counts, not the program's: a share of a peak
is what the work needs over what the chip can do, so recomputation,
padding and the loss re-evaluations the program adds never count.

Each configuration's reference module (``ref``) gives its own counts,
indexed by block, from its sizes (``spec.py`` lists the names); this
module holds only the conventions that compose them (one multiply-add
is 2 operations):

* Backward is twice the forward, so a training step is 3 x forward.
* Serving counts 2 x (matmul weights of the blocks) per token that passes
  the blocks, and 2 x (hidden x vocabulary) per token the head scores;
  the embedding lookup is a read, not a matmul.
* A decode step must read every weight except the embedding table (one
  embedding row), and the K/V of every position it attends to.
"""
from __future__ import annotations

from typing import Any, Dict

F32 = 4  # bytes


def walk_block_flops(ref, conf: Dict[str, Any], i: int, seq: int, tokens: int,
                     epochs: int) -> float:
    """Block ``i`` of the EBFT walk: ``epochs`` training passes (3 x
    forward) over the calibration tokens, plus the teacher's and the
    student's stream advances (1 x forward each)."""
    return (3.0 * epochs + 2.0) * ref.block_fwd_flops_per_token(conf, i, seq) * tokens


def head_matmul_params(ref, conf: Dict[str, Any]) -> int:
    return ref.hidden_size(conf) * conf["vocab_size"]


def serve_request_flops(ref, conf: Dict[str, Any], prompt: int, out: int) -> float:
    """One request of ``out`` generated tokens after a ``prompt``: the
    prompt and all but the last generated token pass the blocks; the head
    scores the prompt's last position and each decode step."""
    passed = prompt + out - 1
    blocks = sum(ref.block_matmul_params(conf, i) for i in range(ref.num_blocks(conf)))
    return 2.0 * blocks * passed + 2.0 * head_matmul_params(ref, conf) * out


def decode_weight_bytes(ref, conf: Dict[str, Any]) -> int:
    """Weights one decode step reads: every block, the final norm, the head
    over the real vocabulary, and one embedding row."""
    d = ref.hidden_size(conf)
    blocks = sum(ref.block_params(conf, i) for i in range(ref.num_blocks(conf)))
    return F32 * (blocks + d + head_matmul_params(ref, conf) + d)


def decode_kv_bytes(ref, conf: Dict[str, Any], attended: int) -> int:
    """K and V of ``attended`` positions, every block (a reference whose
    blocks keep no K/V raises ``ValueError``)."""
    return attended * sum(ref.kv_bytes_per_position(conf, i)
                          for i in range(ref.num_blocks(conf)))


def decode_request_bytes(ref, conf: Dict[str, Any], prompt: int, out: int) -> int:
    """All decode steps of one request: step j (0-based) follows a cache of
    prompt + j positions and attends to prompt + j + 1 of them."""
    steps = out - 1
    attended = steps * (prompt + 1) + steps * (steps - 1) // 2
    return steps * decode_weight_bytes(ref, conf) + decode_kv_bytes(ref, conf, attended)
