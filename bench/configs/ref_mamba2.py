"""Plain float32 reference of a Mamba2 language model (arXiv:2405.21060,
``mamba_ssm``'s ``Mamba2`` mixer with ngroups 1), written from the
published description and independent of ``repro.models``.

Per block: RMSNorm -> input projections z, x, B, C, dt -> causal
depthwise conv (width ``d_conv``, with bias) over [x, B, C], then SiLU ->
dt = softplus(dt + dt_bias), A = -exp(A_log) -> the selective state
recurrence, one token at a time,

    s_t = exp(dt_t * A) * s_{t-1} + dt_t * B_t (x) x_t     (per head: N x P)
    y_t = C_t . s_t + D * x_t

-> y * silu(z) -> RMSNorm (norm after the gate) -> output projection ->
residual. Then a final RMSNorm and the head. The recurrence is the
sequential form, not the chunked dual the program computes; its
backward is taken over chunks of time under ``jax.checkpoint`` so that
it fits. Matmuls run at ``Precision.HIGHEST``.

Departures: the norms use eps 1e-6 as the program computes them
(``mamba_ssm`` defaults to 1e-5), and the head is untied, as the program
has it. ``init`` makes seeded weights in ``repro.models.ssm``'s layout (leaves
stacked over layers under ``blocks``), drawn as ``mamba_ssm``
initialises them (A in [1, 16], dt in [1e-3, 1e-1]).

The module also carries the block's work counts, which
``bench/harness/flops.py`` composes: a block's forward per token is 2 x
its matmul weights, plus the depthwise conv, 2 * K * (d_inner + 2N), and
the recurrence's outer product B (x) x and contraction C . s, 2 * 2 * H
* N * P (the decay multiply is not counted). Norms, biases and
activations are not counted. A block keeps a state, not K/V per
position.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
EPS = 1e-6
TIME_CHUNK = 64  # steps per checkpointed stretch of the recurrence

# where the blocks lie in the program's parameter tree, in visit order
BLOCK_KEYS = ("blocks",)

PRUNABLE: Dict[str, Tuple[str, int]] = {
    "in_z": ("mix_in", 1), "in_x": ("mix_in", 1), "in_B": ("mix_in", 1),
    "in_C": ("mix_in", 1), "in_dt": ("mix_in", 1), "out": ("mix_out", 2),
}


def sizes(c: Dict[str, Any]) -> Dict[str, int]:
    s = c["ssm_cfg"]
    d = c["d_model"]
    di = s["expand"] * d
    V = c["vocab_size"]
    m = c["pad_vocab_size_multiple"]
    return {"d": d, "N": s["d_state"], "P": s["headdim"], "H": di // s["headdim"],
            "K": s["d_conv"], "L": c["n_layer"], "V": V, "Vp": -(-V // m) * m}


def init(key, c: Dict[str, Any]):
    s = sizes(c)
    d, N, P, H, K, L, Vp = (s[k] for k in ("d", "N", "P", "H", "K", "L", "Vp"))
    ch = H * P + 2 * N
    ks = iter(jax.random.split(key, 16))

    def n(shape, scale):
        return jax.random.normal(next(ks), shape, jnp.float32) * scale

    def u(shape, lo, hi):
        return jax.random.uniform(next(ks), shape, jnp.float32, lo, hi)

    dt = jnp.exp(u((L, H), math.log(1e-3), math.log(1e-1)))
    blocks = {
        "ln": {"w": 1.0 + n((L, d), 0.1)},
        "in_z": n((L, d, H, P), 1 / math.sqrt(d)),
        "in_x": n((L, d, H, P), 1 / math.sqrt(d)),
        "in_B": n((L, d, N), 1 / math.sqrt(d)),
        "in_C": n((L, d, N), 1 / math.sqrt(d)),
        "in_dt": n((L, d, H), 1 / math.sqrt(d)),
        "conv_w": u((L, K, ch), -1 / math.sqrt(K), 1 / math.sqrt(K)),
        "conv_b": u((L, ch), -1 / math.sqrt(K), 1 / math.sqrt(K)),
        "A_log": jnp.log(u((L, H), 1.0, 16.0)),
        "D": 1.0 + n((L, H), 0.1),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1(dt)
        "gnorm": {"w": 1.0 + n((L, H * P), 0.1)},
        "out": n((L, H, P, d), 1 / math.sqrt(H * P)),
    }
    return {"embed": {"tok": n((Vp, d), 0.02)}, "blocks": blocks,
            "final_norm": {"w": 1.0 + n((d,), 0.1)},
            "head": {"w": n((d, Vp), 0.02)}}


def rms_norm(x, w):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + EPS) * w


def recurrence(x, dt, A, Bm, Cm, precision=HI):
    """x (B,S,H,P), dt (B,S,H), A (H,), Bm/Cm (B,S,N) -> y (B,S,H,P)."""
    Bsz, S, H, P = x.shape
    N = Bm.shape[-1]
    T = TIME_CHUNK if S % TIME_CHUNK == 0 else S

    def step(s, inp):
        x_t, dt_t, B_t, C_t = inp
        decay = jnp.exp(dt_t * A)[:, :, None, None]
        s = decay * s + (dt_t[:, :, None, None] * B_t[:, None, :, None]
                         * x_t[:, :, None, :])
        return s, jnp.einsum("bn,bhnp->bhp", C_t, s, precision=precision)

    @jax.checkpoint
    def stretch(s, inp):
        return jax.lax.scan(step, s, inp)

    def time_major(a):  # (B, S, ...) -> (S//T, T, B, ...)
        a = jnp.moveaxis(a, 1, 0)
        return a.reshape(S // T, T, *a.shape[1:])

    s0 = jnp.zeros((Bsz, H, N, P), jnp.float32)
    _, y = jax.lax.scan(stretch, s0, tuple(map(time_major, (x, dt, Bm, Cm))))
    return jnp.moveaxis(y.reshape(S, Bsz, H, P), 0, 1)


def block(bp, h, c: Dict[str, Any], taps: bool = False, precision=HI):
    s = sizes(c)
    H, P, N, K = s["H"], s["P"], s["N"], s["K"]
    Bsz, S, _ = h.shape
    u = rms_norm(h, bp["ln"]["w"])
    z = jnp.einsum("bsd,dhp->bshp", u, bp["in_z"], precision=precision)
    x = jnp.einsum("bsd,dhp->bshp", u, bp["in_x"], precision=precision)
    Bm = jnp.matmul(u, bp["in_B"], precision=precision)
    Cm = jnp.matmul(u, bp["in_C"], precision=precision)
    dt = jnp.matmul(u, bp["in_dt"], precision=precision)
    xbc = jnp.concatenate([x.reshape(Bsz, S, H * P), Bm, Cm], axis=-1)
    padded = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    conv = sum(padded[:, i:i + S] * bp["conv_w"][i] for i in range(K))
    xbc = jax.nn.silu(conv + bp["conv_b"])
    x = xbc[..., : H * P].reshape(Bsz, S, H, P)
    Bm, Cm = xbc[..., H * P: H * P + N], xbc[..., H * P + N:]
    dt = jax.nn.softplus(dt + bp["dt_bias"])
    y = recurrence(x, dt, -jnp.exp(bp["A_log"]), Bm, Cm, precision)
    y = y + bp["D"][:, None] * x
    yf = y.reshape(Bsz, S, H * P) * jax.nn.silu(z.reshape(Bsz, S, H * P))
    yf = rms_norm(yf, bp["gnorm"]["w"])
    out = h + jnp.einsum("bshp,hpd->bsd", yf.reshape(Bsz, S, H, P), bp["out"],
                         precision=precision)
    if not taps:
        return out
    return out, {"mix_in": u, "mix_out": yf}


def embed(params, tokens):
    return params["embed"]["tok"][tokens]


def forward(params, tokens, c: Dict[str, Any]):
    h = embed(params, tokens)
    for i in range(c["n_layer"]):
        h = block(jax.tree.map(lambda a: a[i], params["blocks"]), h, c)
    h = rms_norm(h, params["final_norm"]["w"])
    return jnp.matmul(h, params["head"]["w"][:, : c["vocab_size"]], precision=HI)


# -- work counts, per block (every block alike) --------------------------
def _count_sizes(c: Dict[str, Any]):
    s = c["ssm_cfg"]
    d = c["d_model"]
    di = s["expand"] * d
    return d, di, s["d_state"], s["headdim"], di // s["headdim"], s["d_conv"]


def num_blocks(c: Dict[str, Any]) -> int:
    return c["n_layer"]


def hidden_size(c: Dict[str, Any]) -> int:
    return c["d_model"]


def block_matmul_params(c: Dict[str, Any], i: int) -> int:
    """Weights of block ``i``'s matmuls (N_block)."""
    d, di, N, P, H, K = _count_sizes(c)
    return d * (2 * di + 2 * N + H) + di * d


def block_params(c: Dict[str, Any], i: int) -> int:
    """Every weight of block ``i``, norms and biases included."""
    d, di, N, P, H, K = _count_sizes(c)
    return block_matmul_params(c, i) + (K + 1) * (di + 2 * N) + 3 * H + di + d


def block_fwd_flops_per_token(c: Dict[str, Any], i: int, seq: int) -> float:
    d, di, N, P, H, K = _count_sizes(c)
    return 2.0 * block_matmul_params(c, i) + 2.0 * K * (di + 2 * N) + 4.0 * H * N * P


def kv_bytes_per_position(c: Dict[str, Any], i: int) -> int:
    raise ValueError("a Mamba2 block keeps a state, not K/V per position")
