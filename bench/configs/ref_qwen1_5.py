"""Plain float32 reference of a Qwen1.5 decoder (the Hugging Face ``qwen2``
architecture), written from the published description and independent
of ``repro.models``.

Per block: RMSNorm -> q/k/v projections with bias -> rotary embedding
(half-split rotation, theta ``rope_theta``) -> causal softmax attention
scaled by 1/sqrt(head_dim), key/value heads shared over query groups ->
output projection without bias -> residual; RMSNorm -> SwiGLU MLP
(down(silu(gate(x)) * up(x))) -> residual. Then a final RMSNorm and an
untied head. Every matmul runs at ``Precision.HIGHEST`` so that on a TPU
it is float32 and not one bfloat16 pass.

``init`` makes seeded weights in the parameter layout that
``repro.models.transformer`` reads (leaves stacked over layers under
``blocks``, the vocabulary padded to a multiple of 128 rows); the
benchmark gives the same weights to the program and to this reference.

The module also carries the block's work counts, which
``bench/harness/flops.py`` composes: a block's forward per token is 2 x
its matmul weights, plus attention's QK^T and PV over the causal
prefix, on average (S + 1) / 2 keys per query: 2 * 2 * (S + 1) / 2 * H *
hd. Norms, biases, activations and softmax are not counted.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
F32 = 4  # bytes

# where the blocks lie in the program's parameter tree, in visit order
BLOCK_KEYS = ("blocks",)

# prunable leaf (path below a block) -> (the activation it reads, how many
# leading axes of the leaf are its input axes); Wanda scores these
PRUNABLE: Dict[str, Tuple[str, int]] = {
    "attn/wq": ("attn_in", 1), "attn/wk": ("attn_in", 1),
    "attn/wv": ("attn_in", 1), "attn/wo": ("attn_out", 2),
    "mlp/w_gate": ("mlp_in", 1), "mlp/w_up": ("mlp_in", 1),
    "mlp/w_down": ("mlp_hidden", 1),
}


def sizes(c: Dict[str, Any]) -> Dict[str, int]:
    d, H = c["hidden_size"], c["num_attention_heads"]
    return {"d": d, "H": H, "Hkv": c["num_key_value_heads"], "hd": d // H,
            "ff": c["intermediate_size"], "L": c["num_hidden_layers"],
            "V": c["vocab_size"], "Vp": -(-c["vocab_size"] // 128) * 128}


def init(key, c: Dict[str, Any]):
    """Seeded float32 weights. Biases and norm gains are drawn away from
    zero and one so that their paths carry signal."""
    s = sizes(c)
    d, H, Hkv, hd, ff, L, Vp = (s[k] for k in ("d", "H", "Hkv", "hd", "ff", "L", "Vp"))
    ks = iter(jax.random.split(key, 16))

    def n(shape, scale):
        return jax.random.normal(next(ks), shape, jnp.float32) * scale

    blocks = {
        "ln1": {"w": 1.0 + n((L, d), 0.1)},
        "ln2": {"w": 1.0 + n((L, d), 0.1)},
        "attn": {
            "wq": n((L, d, H, hd), 1 / math.sqrt(d)),
            "wk": n((L, d, Hkv, hd), 1 / math.sqrt(d)),
            "wv": n((L, d, Hkv, hd), 1 / math.sqrt(d)),
            "wo": n((L, H, hd, d), 1 / math.sqrt(H * hd)),
            "bq": n((L, H, hd), 0.1),
            "bk": n((L, Hkv, hd), 0.1),
            "bv": n((L, Hkv, hd), 0.1),
        },
        "mlp": {
            "w_gate": n((L, d, ff), 1 / math.sqrt(d)),
            "w_up": n((L, d, ff), 1 / math.sqrt(d)),
            "w_down": n((L, ff, d), 1 / math.sqrt(ff)),
        },
    }
    return {"embed": {"tok": n((Vp, d), 0.02)}, "blocks": blocks,
            "final_norm": {"w": 1.0 + n((d,), 0.1)},
            "head": {"w": n((d, Vp), 0.02)}}


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, theta):
    """x (B, S, heads, hd) at positions 0..S-1."""
    S, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def block(bp, h, c: Dict[str, Any], taps: bool = False, precision=HI):
    """One decoder layer on h (B, S, d); with ``taps`` also the inputs of
    its linear layers, keyed as in :data:`PRUNABLE`."""
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    a = bp["attn"]
    x = rms_norm(h, bp["ln1"]["w"], eps)
    q = jnp.einsum("bsd,dhk->bshk", x, a["wq"], precision=precision) + a["bq"]
    k = jnp.einsum("bsd,dhk->bshk", x, a["wk"], precision=precision) + a["bk"]
    v = jnp.einsum("bsd,dhk->bshk", x, a["wv"], precision=precision) + a["bv"]
    q, k = rope(q, theta), rope(k, theta)
    groups = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, groups, axis=2), jnp.repeat(v, groups, axis=2)
    S = h.shape[1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=precision) / math.sqrt(q.shape[-1])
    causal = jnp.tril(jnp.ones((S, S), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", probs, v, precision=precision)
    h = h + jnp.einsum("bshk,hkd->bsd", o, a["wo"], precision=precision)
    m = bp["mlp"]
    x2 = rms_norm(h, bp["ln2"]["w"], eps)
    hid = jax.nn.silu(jnp.matmul(x2, m["w_gate"], precision=precision)) \
        * jnp.matmul(x2, m["w_up"], precision=precision)
    out = h + jnp.matmul(hid, m["w_down"], precision=precision)
    if not taps:
        return out
    B = h.shape[0]
    return out, {"attn_in": x, "attn_out": o.reshape(B, S, -1),
                 "mlp_in": x2, "mlp_hidden": hid}


def embed(params, tokens):
    return params["embed"]["tok"][tokens]


def forward(params, tokens, c: Dict[str, Any]):
    """tokens (B, S) -> logits (B, S, vocab_size) over the real vocabulary."""
    h = embed(params, tokens)
    for i in range(c["num_hidden_layers"]):
        h = block(jax.tree.map(lambda a: a[i], params["blocks"]), h, c)
    h = rms_norm(h, params["final_norm"]["w"], c["rms_norm_eps"])
    return jnp.matmul(h, params["head"]["w"][:, : c["vocab_size"]], precision=HI)


# -- work counts, per block (every block alike) --------------------------
def num_blocks(c: Dict[str, Any]) -> int:
    return c["num_hidden_layers"]


def hidden_size(c: Dict[str, Any]) -> int:
    return c["hidden_size"]


def block_matmul_params(c: Dict[str, Any], i: int) -> int:
    """Weights of block ``i``'s matmuls (N_block)."""
    s = sizes(c)
    d, H, Hkv, hd, ff = s["d"], s["H"], s["Hkv"], s["hd"], s["ff"]
    return d * hd * (H + 2 * Hkv) + H * hd * d + 3 * d * ff


def block_params(c: Dict[str, Any], i: int) -> int:
    """Every weight of block ``i``, norms and biases included."""
    s = sizes(c)
    return block_matmul_params(c, i) + s["hd"] * (s["H"] + 2 * s["Hkv"]) + 2 * s["d"]


def block_fwd_flops_per_token(c: Dict[str, Any], i: int, seq: int) -> float:
    s = sizes(c)
    return 2.0 * block_matmul_params(c, i) + 2.0 * (seq + 1) * s["H"] * s["hd"]


def kv_bytes_per_position(c: Dict[str, Any], i: int) -> int:
    """K and V of one cached position in block ``i``, float32."""
    s = sizes(c)
    return F32 * 2 * s["Hkv"] * s["hd"]
