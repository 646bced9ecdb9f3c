"""Plain float32 reference of the program's ``moe`` family at test sizes:
a DeepSeek-MoE-style decoder, written from ``repro.models.moe``'s
documented semantics and independent of ``repro.models``.

Blocks, in visit order: ``first_k_dense_replace`` dense blocks, then MoE
blocks. Every block: RMSNorm -> q/k/v projections without bias -> rotary
embedding (half-split rotation, theta ``rope_theta``) -> causal softmax
attention scaled by 1/sqrt(head_dim) -> output projection -> residual;
RMSNorm -> the MLP -> residual. A dense block's MLP is SwiGLU
(down(silu(gate(x)) * up(x))). An MoE block's MLP is

    probs = softmax(x @ router)                      over the routed experts
    top-k of probs, gates renormalised over those k
    out = sum over the k chosen experts of gate_e * expert_e(x)
          + shared(x)

with SwiGLU experts of width ``moe_intermediate_size`` and the
``n_shared_experts`` shared experts as one SwiGLU MLP of that many times
the width, which every token passes. The reference is dropless: every
token reaches each of its k experts. The program drops tokens beyond an
expert's capacity, so the configuration sets ``moe_capacity_factor`` to
``n_routed_experts / num_experts_per_tok``, at which the capacity is the
whole token group and no token drops. The router is not pruned. Every
matmul runs at ``Precision.HIGHEST``.

``init`` makes seeded weights in the layout ``repro.models.moe`` reads:
``dense_blocks`` and ``moe_blocks``, each stacked over its blocks.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
F32 = 4  # bytes
EPS = 1e-6

BLOCK_KEYS = ("dense_blocks", "moe_blocks")

# prunable leaf -> (tap, input axes[, "experts": the leaf's leading axis
# and the tap's index the routed experts]); a dense block has the mlp
# leaves, an MoE block the moe ones
PRUNABLE: Dict[str, Tuple] = {
    "attn/wq": ("attn_in", 1), "attn/wk": ("attn_in", 1),
    "attn/wv": ("attn_in", 1), "attn/wo": ("attn_out", 2),
    "mlp/w_gate": ("mlp_in", 1), "mlp/w_up": ("mlp_in", 1),
    "mlp/w_down": ("mlp_hidden", 1),
    "moe/shared/w_gate": ("mlp_in", 1), "moe/shared/w_up": ("mlp_in", 1),
    "moe/shared/w_down": ("shared_hidden", 1),
    "moe/experts/w_gate": ("expert_in", 1, "experts"),
    "moe/experts/w_up": ("expert_in", 1, "experts"),
    "moe/experts/w_down": ("expert_hidden", 1, "experts"),
}


def sizes(c: Dict[str, Any]) -> Dict[str, int]:
    d, H = c["hidden_size"], c["num_attention_heads"]
    return {"d": d, "H": H, "Hkv": c["num_key_value_heads"], "hd": d // H,
            "ff": c["intermediate_size"], "fe": c["moe_intermediate_size"],
            "E": c["n_routed_experts"], "k": c["num_experts_per_tok"],
            "S": c["n_shared_experts"], "L": c["num_hidden_layers"],
            "Ld": c["first_k_dense_replace"],
            "Vp": -(-c["vocab_size"] // 128) * 128}


def init(key, c: Dict[str, Any]):
    """Seeded float32 weights; norm gains drawn away from one."""
    s = sizes(c)
    d, H, Hkv, hd, E, Vp = (s[k] for k in ("d", "H", "Hkv", "hd", "E", "Vp"))
    Ld, Lm = s["Ld"], s["L"] - s["Ld"]
    ks = iter(jax.random.split(key, 32))

    def n(shape, scale):
        return jax.random.normal(next(ks), shape, jnp.float32) * scale

    def norm(L):
        return {"w": 1.0 + n((L, d), 0.1)}

    def attn(L):
        return {"wq": n((L, d, H, hd), 1 / math.sqrt(d)),
                "wk": n((L, d, Hkv, hd), 1 / math.sqrt(d)),
                "wv": n((L, d, Hkv, hd), 1 / math.sqrt(d)),
                "wo": n((L, H, hd, d), 1 / math.sqrt(H * hd))}

    def mlp(lead, ff):
        return {"w_gate": n((*lead, d, ff), 1 / math.sqrt(d)),
                "w_up": n((*lead, d, ff), 1 / math.sqrt(d)),
                "w_down": n((*lead, ff, d), 1 / math.sqrt(ff))}

    dense = {"ln1": norm(Ld), "ln2": norm(Ld), "attn": attn(Ld), "mlp": mlp((Ld,), s["ff"])}
    moe = {"ln1": norm(Lm), "ln2": norm(Lm), "attn": attn(Lm),
           "moe": {"router": {"w": n((Lm, d, E), 1 / math.sqrt(d))},
                   "experts": mlp((Lm, E), s["fe"]),
                   "shared": mlp((Lm,), s["S"] * s["fe"])}}
    return {"embed": {"tok": n((Vp, d), 0.02)}, "dense_blocks": dense, "moe_blocks": moe,
            "final_norm": {"w": 1.0 + n((d,), 0.1)}, "head": {"w": n((d, Vp), 0.02)}}


def rms_norm(x, w):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + EPS) * w


def rope(x, theta):
    """x (B, S, heads, hd) at positions 0..S-1."""
    S, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def attention(a, x, theta, precision):
    q = rope(jnp.einsum("bsd,dhk->bshk", x, a["wq"], precision=precision), theta)
    k = rope(jnp.einsum("bsd,dhk->bshk", x, a["wk"], precision=precision), theta)
    v = jnp.einsum("bsd,dhk->bshk", x, a["wv"], precision=precision)
    groups = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, groups, axis=2), jnp.repeat(v, groups, axis=2)
    S = x.shape[1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=precision) / math.sqrt(q.shape[-1])
    probs = jax.nn.softmax(jnp.where(jnp.tril(jnp.ones((S, S), bool)), scores, -jnp.inf),
                           axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", probs, v, precision=precision)
    return jnp.einsum("bshk,hkd->bsd", o, a["wo"], precision=precision), o


def swiglu_hidden(m, x, precision):
    return jax.nn.silu(jnp.matmul(x, m["w_gate"], precision=precision)) \
        * jnp.matmul(x, m["w_up"], precision=precision)


def moe_mlp(m, x, c, precision):
    """(output, taps) of the routed and shared experts on x (B, S, d)."""
    E, k = c["n_routed_experts"], c["num_experts_per_tok"]
    probs = jax.nn.softmax(jnp.matmul(x, m["router"]["w"], precision=precision), axis=-1)
    top, idx = jax.lax.top_k(probs, k)
    gates = top / jnp.sum(top, axis=-1, keepdims=True)
    chosen = jax.nn.one_hot(idx, E, dtype=x.dtype)                 # (B, S, k, E)
    weight = jnp.sum(chosen * gates[..., None], axis=-2)           # (B, S, E)
    routed = jnp.moveaxis(jnp.sum(chosen, axis=-2), -1, 0)[..., None]  # (E, B, S, 1)
    ex = m["experts"]
    hid = jax.nn.silu(jnp.einsum("bsd,edf->ebsf", x, ex["w_gate"], precision=precision)) \
        * jnp.einsum("bsd,edf->ebsf", x, ex["w_up"], precision=precision)
    eout = jnp.einsum("ebsf,efd->ebsd", hid, ex["w_down"], precision=precision)
    out = jnp.einsum("ebsd,bse->bsd", eout, weight, precision=precision)
    sh = swiglu_hidden(m["shared"], x, precision)
    out = out + jnp.matmul(sh, m["shared"]["w_down"], precision=precision)
    return out, {"shared_hidden": sh, "expert_in": routed * x[None],
                 "expert_hidden": routed * hid}


def block(bp, h, c: Dict[str, Any], taps: bool = False, precision=HI):
    """One block, dense or MoE by its leaves, on h (B, S, d); with
    ``taps`` also the inputs of its linear layers, keyed as in
    :data:`PRUNABLE` (an expert's inputs zero where a token is not routed
    to it)."""
    x = rms_norm(h, bp["ln1"]["w"])
    att, o = attention(bp["attn"], x, c["rope_theta"], precision)
    h = h + att
    x2 = rms_norm(h, bp["ln2"]["w"])
    tapped = {"attn_in": x, "attn_out": o.reshape(*o.shape[:2], -1), "mlp_in": x2}
    if "moe" in bp:
        mlp_out, more = moe_mlp(bp["moe"], x2, c, precision)
    else:
        hid = swiglu_hidden(bp["mlp"], x2, precision)
        mlp_out, more = jnp.matmul(hid, bp["mlp"]["w_down"], precision=precision), \
            {"mlp_hidden": hid}
    out = h + mlp_out
    return (out, {**tapped, **more}) if taps else out


def embed(params, tokens):
    return params["embed"]["tok"][tokens]


def forward(params, tokens, c: Dict[str, Any]):
    """tokens (B, S) -> logits (B, S, vocab_size) over the real vocabulary."""
    h = embed(params, tokens)
    for key in BLOCK_KEYS:
        for i in range(jax.tree.leaves(params[key])[0].shape[0]):
            h = block(jax.tree.map(lambda a: a[i], params[key]), h, c)
    h = rms_norm(h, params["final_norm"]["w"])
    return jnp.matmul(h, params["head"]["w"][:, : c["vocab_size"]], precision=HI)


# -- work counts, per block: dense blocks first, then MoE blocks ---------
def num_blocks(c: Dict[str, Any]) -> int:
    return c["num_hidden_layers"]


def hidden_size(c: Dict[str, Any]) -> int:
    return c["hidden_size"]


def _attn_params(s) -> int:
    return s["d"] * s["hd"] * (s["H"] + 2 * s["Hkv"]) + s["H"] * s["hd"] * s["d"]


def block_matmul_params(c: Dict[str, Any], i: int) -> int:
    """Weights of the matmuls one token passes in block ``i``: in an MoE
    block the router, the shared experts and its k routed experts."""
    s = sizes(c)
    if i < s["Ld"]:
        return _attn_params(s) + 3 * s["d"] * s["ff"]
    return _attn_params(s) + s["d"] * s["E"] + 3 * s["d"] * s["fe"] * (s["k"] + s["S"])


def block_params(c: Dict[str, Any], i: int) -> int:
    """Every weight of block ``i``: in an MoE block every routed expert."""
    s = sizes(c)
    if i < s["Ld"]:
        return block_matmul_params(c, i) + 2 * s["d"]
    return (_attn_params(s) + s["d"] * s["E"] + 3 * s["d"] * s["fe"] * (s["E"] + s["S"])
            + 2 * s["d"])


def block_fwd_flops_per_token(c: Dict[str, Any], i: int, seq: int) -> float:
    s = sizes(c)
    return 2.0 * block_matmul_params(c, i) + 2.0 * (seq + 1) * s["H"] * s["hd"]


def kv_bytes_per_position(c: Dict[str, Any], i: int) -> int:
    s = sizes(c)
    return F32 * 2 * s["Hkv"] * s["hd"]
