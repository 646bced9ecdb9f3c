"""The comparison that decides ``correct`` for a walk cell.

The walk's first call (set-up's warm-up: the window's own entry, its
compiled programs and its sizes) is followed by a plain float32
reference at ``Precision.HIGHEST`` that walks every block as the paper
does: the teacher stream through the dense blocks; each block tuned with
Adam over the same microbatches in the same order under the same plateau
rule; the student stream advanced through the reference's own tuned
block into the next. Compared, each as the worst over the blocks:

  history       per-epoch mean losses                      (relative gap)
  change        per-leaf norm of the weight change, worst leaf:
                |prog - ref| / max(ref leaf, median leaf of the block);
                leaves whose first reference gradient is under 1e-3 of
                the block's median leaf's move by round-off alone and are
                left out (``notes`` names them)
  stream        the loss before tuning of every block after the first,
                which reads the student stream advanced through the tuned
                blocks before it                            (relative gap)
  loss_after    the loss after tuning                      (relative gap)
  masked_nonzero  tuned weights not zero where their mask is, every
                block, first call and last window call  (count)

Block 0's loss before tuning (``loss_before``) and the epochs run
(``epochs``) are read too; the cell's limits file says which numbers are
compared (PERF.md gives the readings).

The reference reads only what the benchmark made: the seeded weights,
the Wanda masks and the calibration tokens.
"""
from __future__ import annotations

from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from harness import layout

GRAD_FLOOR = 1e-3  # leaves under this share of the median leaf's first gradient


def plateau(history: List[float], patience: int, rel_tol: float) -> bool:
    """The paper's convergence rule as the program states it: the best of
    the last ``patience`` epochs improves on the best before by less than
    ``rel_tol``."""
    if patience <= 0 or len(history) < patience + 1:
        return False
    return min(history[-patience:]) > min(history[:-patience]) * (1.0 - rel_tol)


def leaf_paths(tree) -> List[str]:
    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def named(tree) -> Dict[str, float]:
    return dict(zip(leaf_paths(tree), map(float, jax.tree.leaves(jax.device_get(tree)))))


@jax.jit
def change_norms(tuned_block, start_block):
    return jax.tree.map(
        lambda a, b: jnp.linalg.norm((a.astype(jnp.float32) - b.astype(jnp.float32)).ravel()),
        tuned_block, start_block)


@jax.jit
def masked_nonzero(tuned, masks):
    """Weights not zero where their mask is, over the whole tree (the
    masks outside the blocks are scalar ones)."""
    return sum(jax.tree.leaves(jax.tree.map(lambda t, m: jnp.sum((m == 0) & (t != 0)),
                                            tuned, masks)))


def program_summary(ref, tuned, pruned, masks, reports) -> Dict[str, Any]:
    """What the check keeps of the program's first call (host values)."""
    per_block = []
    for i, r in enumerate(reports):
        ti, si = layout.block(ref, tuned, i), layout.block(ref, pruned, i)
        per_block.append({"loss_before": r.loss_before, "loss_after": r.loss_after,
                          "history": list(r.history[1:]), "epochs": r.epochs_run,
                          "change": named(change_norms(ti, si))})
    return {"per_block": per_block, "masked_nonzero": int(masked_nonzero(tuned, masks))}


def reference(ref, conf, dense, pruned, masks, tokens, ecfg: Dict[str, Any]):
    """The whole walk, in plain float32 at HIGHEST."""
    mb = ecfg["microbatch"]
    lr, b1, b2, eps = ecfg["lr"], 0.9, 0.999, 1e-8

    fwd = jax.jit(lambda bp, h: ref.block(bp, h, conf))

    @jax.jit
    def loss(bw, m, h, t):
        out = ref.block(jax.tree.map(jnp.multiply, bw, m), h, conf)
        return jnp.mean(jnp.square(out - t))

    @jax.jit
    def step(bw, opt, n, m, h, t):
        val, g = jax.value_and_grad(loss)(bw, m, h, t)
        mo = jax.tree.map(lambda a, b: b1 * a + (1 - b1) * b, opt[0], g)
        vo = jax.tree.map(lambda a, b: b2 * a + (1 - b2) * b * b, opt[1], g)
        c1, c2 = 1 - b1 ** n, 1 - b2 ** n
        bw = jax.tree.map(lambda p, a, b: p - lr * (a / c1) / (jnp.sqrt(b / c2) + eps),
                          bw, mo, vo)
        return bw, (mo, vo), val, g

    def mean_loss(bw, m, hs_, ts_):
        return float(np.mean([float(loss(bw, m, x, t)) for x, t in zip(hs_, ts_)]))

    h = jax.jit(ref.embed)(dense, jnp.asarray(tokens))
    student = [h[j:j + mb] for j in range(0, h.shape[0], mb)]
    teacher = student
    per_block = []
    for i in range(layout.count(ref, masks)):
        d, s, m = (layout.block(ref, t, i) for t in (dense, pruned, masks))
        teacher = [fwd(d, x) for x in teacher]
        bw = s
        before = mean_loss(bw, m, student, teacher)
        opt = (jax.tree.map(jnp.zeros_like, bw), jax.tree.map(jnp.zeros_like, bw))
        history, first_grad, n = [before], None, 0
        for _ in range(ecfg["epochs"]):
            losses = []
            for x, t in zip(student, teacher):
                n += 1
                bw, opt, val, g = step(bw, opt, jnp.float32(n), m, x, t)
                if first_grad is None:
                    first_grad = named(jax.tree.map(lambda a: jnp.linalg.norm(a.ravel()), g))
                losses.append(float(val))
            history.append(float(np.mean(losses)))
            if plateau(history, ecfg["patience"], ecfg["rel_tol"]):
                break
        tuned = jax.tree.map(jnp.multiply, bw, m)
        per_block.append({"loss_before": before, "loss_after": mean_loss(bw, m, student, teacher),
                       "history": history[1:], "epochs": len(history) - 1,
                       "change": named(change_norms(tuned, s)), "first_grad": first_grad})
        student = [fwd(tuned, x) for x in student]
        del bw, opt, tuned
    return {"per_block": per_block}


def rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-30)


def moved(first_grad: Dict[str, float]) -> List[str]:
    """Leaves whose first reference gradient is at least ``GRAD_FLOOR`` of
    the block's median leaf's."""
    med = float(np.median(list(first_grad.values())))
    return [k for k, g in first_grad.items() if g >= GRAD_FLOOR * med]


def dropped(ref: Dict[str, Any]) -> Dict[str, float]:
    """Per block, the leaves the change leaves out, with their first
    reference gradient over the block's median leaf's."""
    out = {}
    for i, b in enumerate(ref["per_block"]):
        g = b["first_grad"]
        med = float(np.median(list(g.values())))
        keep = set(moved(g))
        out.update({f"{i}/{k}": v / med for k, v in g.items() if k not in keep})
    return out


def by_block(prog: Dict[str, Any], ref: Dict[str, Any]) -> List[Dict[str, Any]]:
    """Per block: each epoch's loss gap, the worst leaf's change gap, the
    gaps of the losses before and after tuning, the epochs' difference."""
    out = []
    for p, r in zip(prog["per_block"], ref["per_block"]):
        keep = moved(r["first_grad"])
        med_c = float(np.median([r["change"][k] for k in keep]))
        common = min(len(p["history"]), len(r["history"]))
        out.append({
            "history": [rel(a, b) for a, b in zip(p["history"][:common], r["history"][:common])],
            "change": float(np.max([abs(p["change"][k] - r["change"][k])
                                    / max(r["change"][k], med_c) for k in keep])),
            "before": rel(p["loss_before"], r["loss_before"]),
            "after": rel(p["loss_after"], r["loss_after"]),
            "epochs": abs(p["epochs"] - r["epochs"])})
    return out


def numbers(prog: Dict[str, Any], ref: Dict[str, Any], nonzero_last: int) -> Dict[str, float]:
    """Each number is the worst over the blocks; a NaN reads as NaN, and a
    walk that reports other blocks than the reference's reads inf."""
    blocks = by_block(prog, ref)
    short = [np.inf] if len(prog["per_block"]) != len(ref["per_block"]) else []
    return {
        "loss_before": blocks[0]["before"],
        "history": float(np.max([g for b in blocks for g in b["history"]] + short)),
        "epochs": float(sum(b["epochs"] for b in blocks)),
        "loss_after": float(np.max([b["after"] for b in blocks])),
        "change": float(np.max([b["change"] for b in blocks] + short)),
        "stream": float(np.max([b["before"] for b in blocks[1:]], initial=0.0)),
        "masked_nonzero": float(prog["masked_nonzero"] + nonzero_last),
    }
