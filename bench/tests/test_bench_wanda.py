"""The benchmark's Wanda masks on an MoE block (bench_tiny's tiny_moe):
each routed expert keeps (1 - sparsity) of its inputs per output unit,
ranked by |W| times the norm of that input over the tokens routed to
that expert alone."""
import bench_tiny as T
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import layout, wanda

SPARSITY = 0.5
SLACK = 1e-5  # relative: the stream here is not computed in wanda's order


@pytest.fixture(scope="module")
def moe_block():
    """(masks of the first MoE block, its weights, the MoE layer's input
    over every calibration token, which expert each token is routed to)."""
    conf = T.TINY_MOE
    ref = T.reference(conf)
    params = jax.jit(lambda k: ref.init(k, conf))(jax.random.PRNGKey(5))
    tokens = np.random.default_rng(0).integers(0, conf["vocab_size"], (16, 32))
    masks, _ = wanda.masks(ref, conf, params, tokens, SPARSITY, 8)
    assert layout.sizes(ref, params) == [1, 2]
    # block 1 is scored on the output of the pruned block 0
    h = ref.block(jax.tree.map(jnp.multiply, layout.block(ref, params, 0),
                               layout.block(ref, masks, 0)), ref.embed(params, tokens), conf)
    bp = layout.block(ref, params, 1)
    _, taps = ref.block(bp, h, conf, taps=True)
    x = np.asarray(taps["mlp_in"], np.float64).reshape(-1, conf["hidden_size"])
    logits = x @ np.asarray(bp["moe"]["router"]["w"], np.float64)
    top = np.argsort(-logits, axis=-1)[:, : conf["num_experts_per_tok"]]
    routed = np.zeros(logits.shape, bool)
    np.put_along_axis(routed, top, True, axis=-1)
    return layout.block(ref, masks, 1)["moe"]["experts"], bp["moe"]["experts"], x, routed


def ranked(mask, w, col_norm):
    """Whether every kept input of each output unit scores at least as
    high as every input it drops (to ``SLACK``)."""
    score = np.abs(np.asarray(w, np.float64)) * col_norm[:, None]
    kept = np.where(mask == 1, score, np.inf).min(axis=0)
    dropped = np.where(mask == 0, score, -np.inf).max(axis=0)
    return bool(np.all(kept >= dropped * (1 - SLACK)))


def test_each_expert_keeps_its_share_scored_on_its_own_tokens(moe_block):
    experts, weights, x, routed = moe_block
    mask = np.asarray(experts["w_gate"])
    w = weights["w_gate"]
    E, d, _ = mask.shape
    keep = round(d * (1 - SPARSITY))
    assert routed.sum(axis=0).min() > 0  # every expert has tokens to be scored on
    for e in range(E):
        assert set(np.unique(mask[e])) == {0.0, 1.0}
        assert np.all(mask[e].sum(axis=0) == keep)  # per output unit
        assert ranked(mask[e], w[e], np.linalg.norm(x[routed[:, e]], axis=0))
    # scored on every token, or on another expert's, the ranking breaks
    assert not all(ranked(mask[e], w[e], np.linalg.norm(x, axis=0)) for e in range(E))
    assert not all(ranked(mask[e], w[e], np.linalg.norm(x[routed[:, (e + 1) % E]], axis=0))
                   for e in range(E))


def test_expert_down_projections_keep_their_share(moe_block):
    experts, _, _, _ = moe_block
    mask = np.asarray(experts["w_down"])  # (E, expert width, d)
    keep = round(mask.shape[1] * (1 - SPARSITY))
    assert np.all(mask.sum(axis=1) == keep)
