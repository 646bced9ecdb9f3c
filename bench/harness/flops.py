"""Operations and bytes the algorithm requires, from a configuration's sizes.

These are the yardstick's counts, not the program's: a share of a peak
is what the work needs over what the chip can do, so recomputation,
padding and the loss re-evaluations the program adds never count.

Conventions (one multiply-add is 2 operations):

* A block's forward per token is 2 x its matmul weights, plus
  - attention (dense family): QK^T and PV over the causal prefix, on
    average (S + 1) / 2 keys per query: 2 * 2 * (S + 1) / 2 * H * hd;
  - the Mamba2 mixer (ssm family): the depthwise conv, 2 * K * (d_inner +
    2N), and the recurrence's outer product B (x) x and contraction C . s,
    2 * 2 * H * N * P (the decay multiply is not counted).
  Norms, biases, activations and softmax are not counted.
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


def family(conf: Dict[str, Any]) -> str:
    return conf["program_fields"]["family"]


def _dense(conf):
    d, H = conf["hidden_size"], conf["num_attention_heads"]
    return d, H, conf["num_key_value_heads"], d // H, conf["intermediate_size"]


def _ssm(conf):
    s = conf["ssm_cfg"]
    d = conf["d_model"]
    di = s["expand"] * d
    return d, di, s["d_state"], s["headdim"], di // s["headdim"], s["d_conv"]


def layers(conf: Dict[str, Any]) -> int:
    return conf["num_hidden_layers"] if family(conf) == "dense" else conf["n_layer"]


def block_matmul_params(conf: Dict[str, Any]) -> int:
    """Weights of one block's matmuls (N_block)."""
    if family(conf) == "dense":
        d, H, Hkv, hd, ff = _dense(conf)
        return d * hd * (H + 2 * Hkv) + H * hd * d + 3 * d * ff
    if family(conf) == "ssm":
        d, di, N, P, H, K = _ssm(conf)
        return d * (2 * di + 2 * N + H) + di * d
    raise ValueError(f"no counts for family {family(conf)!r}")


def block_params(conf: Dict[str, Any]) -> int:
    """Every weight of one block, norms and biases included."""
    if family(conf) == "dense":
        d, H, Hkv, hd, ff = _dense(conf)
        return block_matmul_params(conf) + hd * (H + 2 * Hkv) + 2 * d
    d, di, N, P, H, K = _ssm(conf)
    return block_matmul_params(conf) + (K + 1) * (di + 2 * N) + 3 * H + di + d


def block_fwd_flops_per_token(conf: Dict[str, Any], seq: int) -> float:
    base = 2.0 * block_matmul_params(conf)
    if family(conf) == "dense":
        d, H, Hkv, hd, ff = _dense(conf)
        return base + 2.0 * (seq + 1) * H * hd
    d, di, N, P, H, K = _ssm(conf)
    return base + 2.0 * K * (di + 2 * N) + 4.0 * H * N * P


def walk_block_flops(conf: Dict[str, Any], seq: int, tokens: int,
                     epochs: int) -> float:
    """One block of the EBFT walk: ``epochs`` training passes (3 x forward)
    over the calibration tokens, plus the teacher's and the student's
    stream advances (1 x forward each)."""
    return (3.0 * epochs + 2.0) * block_fwd_flops_per_token(conf, seq) * tokens


def head_matmul_params(conf: Dict[str, Any]) -> int:
    d = conf["hidden_size"] if family(conf) == "dense" else conf["d_model"]
    return d * conf["vocab_size"]


def serve_request_flops(conf: Dict[str, Any], prompt: int, out: int) -> float:
    """One request of ``out`` generated tokens after a ``prompt``: the
    prompt and all but the last generated token pass the blocks; the head
    scores the prompt's last position and each decode step."""
    passed = prompt + out - 1
    return (2.0 * layers(conf) * block_matmul_params(conf) * passed
            + 2.0 * head_matmul_params(conf) * out)


def decode_weight_bytes(conf: Dict[str, Any]) -> int:
    """Weights one decode step reads: every block, the final norm, the head
    over the real vocabulary, and one embedding row."""
    d = conf["hidden_size"] if family(conf) == "dense" else conf["d_model"]
    return F32 * (layers(conf) * block_params(conf) + d
                  + head_matmul_params(conf) + d)


def decode_kv_bytes(conf: Dict[str, Any], attended: int) -> int:
    """K and V of ``attended`` positions, every layer."""
    if family(conf) != "dense":
        raise ValueError("K/V bytes are defined for attention models only")
    d, H, Hkv, hd, ff = _dense(conf)
    return F32 * 2 * layers(conf) * attended * Hkv * hd


def decode_request_bytes(conf: Dict[str, Any], prompt: int, out: int) -> int:
    """All decode steps of one request: step j (0-based) follows a cache of
    prompt + j positions and attends to prompt + j + 1 of them."""
    steps = out - 1
    attended = steps * (prompt + 1) + steps * (steps - 1) // 2
    return steps * decode_weight_bytes(conf) + decode_kv_bytes(conf, attended)
