"""Pass 2 — mask invariants.

EBFT freezes masks and trains only surviving weights; correctness requires
that pruned slots get **exactly zero gradient** (PAPER.md Eq. 4). That
holds iff the forward graph multiplies every prunable weight by its mask
*before* any contraction: d(loss)/dW then carries the mask factor by the
chain rule. This pass proves the property statically on the traced jaxpr
of ``reconstruction.block_loss``:

  * every jaxpr input corresponding to a prunable weight leaf is tainted
    ``W`` (unmasked weight), every mask leaf ``M``;
  * taint flows through all ops; a ``mul`` whose operands carry ``W`` and
    ``M`` produces ``WM`` (masked weight) and *clears* ``W``;
  * any ``dot_general`` / ``conv_general_dilated`` consuming a value still
    tainted ``W`` is an unmasked contraction -> MSK001 (error).

The second half validates concrete mask pytrees: binary values (MSK002)
and exact N:M group counts along the reduction axis (MSK003).
"""
from __future__ import annotations

from typing import Any, Dict, FrozenSet, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend import core as jcore

from repro.analysis.findings import Finding
from repro.analysis.jaxpr_utils import _as_jaxpr
from repro.sparsity import sparse_params as SP

Taint = FrozenSet[str]
_EMPTY: Taint = frozenset()
_W: Taint = frozenset({"W"})
_M: Taint = frozenset({"M"})
_WM: Taint = frozenset({"WM"})

_CONTRACTIONS = ("dot_general", "conv_general_dilated")
_SUBJAXPR_KEYS = ("jaxpr", "call_jaxpr", "fun_jaxpr")
_MAX_FIXPOINT = 8


def _taint_jaxpr(
    jaxpr,
    in_taints: Sequence[Taint],
    sink: Dict[Tuple[str, str], Finding],
    where: str,
    config: str,
) -> List[Taint]:
    """Propagate taints through one jaxpr; returns outvar taints. Findings
    are deduplicated into ``sink`` (fixpoint iterations revisit eqns)."""
    jaxpr = _as_jaxpr(jaxpr)
    env: Dict[Any, Taint] = {}

    def read(atom) -> Taint:
        if isinstance(atom, jcore.Literal):
            return _EMPTY
        return env.get(atom, _EMPTY)

    def write(var, taint: Taint) -> None:
        env[var] = taint  # a dropped output is written but never read

    if len(jaxpr.invars) != len(in_taints):
        raise ValueError(
            f"{where}: taint arity mismatch "
            f"({len(jaxpr.invars)} invars, {len(in_taints)} taints)"
        )
    for v, t in zip(jaxpr.invars, in_taints):
        write(v, t)
    for v in jaxpr.constvars:
        write(v, _EMPTY)

    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        ts = [read(a) for a in eqn.invars]
        union: Taint = frozenset().union(*ts) if ts else _EMPTY

        sub_out = _dispatch_subjaxpr(eqn, ts, sink, where, config)
        if sub_out is not None:
            for v, t in zip(eqn.outvars, sub_out):
                write(v, t)
            continue

        if name in _CONTRACTIONS:
            for pos, t in enumerate(ts):
                if "W" in t:
                    key = ("MSK001", f"{where}:{name}#{pos}")
                    sink.setdefault(
                        key,
                        Finding(
                            code="MSK001",
                            severity="error",
                            pass_name="masks",
                            config=config,
                            location=where,
                            message=(
                                f"unmasked prunable weight reaches a {name} "
                                f"(operand {pos}) — pruned slots would receive "
                                "nonzero gradient; multiply by the frozen mask "
                                "before the contraction (apply_masks)"
                            ),
                        ),
                    )
            out_t = union
        elif name == "mul" and "W" in union and "M" in union:
            # the mask multiply: W is neutralized, the product is masked
            out_t = (union - {"W", "M"}) | {"WM"}
        else:
            out_t = union

        for v in eqn.outvars:
            write(v, out_t)

    return [read(v) for v in jaxpr.outvars]


def _dispatch_subjaxpr(eqn, ts, sink, where, config):
    """Handle call/control-flow primitives; returns outvar taints or None
    for plain primitives."""
    name = eqn.primitive.name
    params = eqn.params

    if name == "scan":
        sub = _as_jaxpr(params["jaxpr"])
        nc, ncar = params["num_consts"], params["num_carry"]
        cur = list(ts)
        out = [_EMPTY] * len(eqn.outvars)
        for _ in range(_MAX_FIXPOINT):
            out = _taint_jaxpr(sub, cur, sink, f"{where}/scan", config)
            new_carry = [cur[nc + i] | out[i] for i in range(ncar)]
            if new_carry == cur[nc:nc + ncar]:
                break
            cur[nc:nc + ncar] = new_carry
        return out

    if name == "while":
        cond = _as_jaxpr(params["cond_jaxpr"])
        body = _as_jaxpr(params["body_jaxpr"])
        cn, bn = params["cond_nconsts"], params["body_nconsts"]
        cond_consts, body_consts = ts[:cn], ts[cn:cn + bn]
        carry = list(ts[cn + bn:])
        for _ in range(_MAX_FIXPOINT):
            _taint_jaxpr(cond, cond_consts + carry, sink, f"{where}/while.cond", config)
            out = _taint_jaxpr(body, body_consts + carry, sink, f"{where}/while.body", config)
            new_carry = [c | o for c, o in zip(carry, out)]
            if new_carry == carry:
                break
            carry = new_carry
        return carry

    if name == "cond":
        outs = None
        for bi, br in enumerate(params["branches"]):
            o = _taint_jaxpr(_as_jaxpr(br), ts[1:], sink, f"{where}/cond.{bi}", config)
            outs = o if outs is None else [a | b for a, b in zip(outs, o)]
        return outs

    for key in _SUBJAXPR_KEYS:
        if key in params and params[key] is not None:
            sub = _as_jaxpr(params[key])
            if len(sub.invars) == len(ts):
                return _taint_jaxpr(sub, ts, sink, f"{where}/{name}", config)
            # unknown calling convention: be conservative, union everything
            union = frozenset().union(*ts) if ts else _EMPTY
            return [union] * len(eqn.outvars)

    return None


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------
def check_masked_fn(
    fn, weights, masks, *args, where: str = "block_loss", config: str = ""
) -> List[Finding]:
    """Trace ``fn(weights, masks, *args)`` and verify no prunable weight
    reaches a contraction unmasked. ``weights`` / ``masks`` are matching
    pytrees (masks as produced by the pruning layer: full-shape on
    prunable leaves, scalar elsewhere)."""
    closed = jax.make_jaxpr(fn)(weights, masks, *args)

    w_flat = jax.tree_util.tree_flatten_with_path(weights)[0]
    prunable = [SP.is_prunable(path, leaf) for path, leaf in w_flat]
    m_flat = jax.tree_util.tree_leaves(masks)
    if len(m_flat) != len(prunable):
        raise ValueError("weights and masks pytrees do not match")

    taints: List[Taint] = []
    taints += [_W if p else _EMPTY for p in prunable]
    taints += [_M if p else _EMPTY for p in prunable]
    rest = jax.tree_util.tree_leaves(args)
    taints += [_EMPTY] * len(rest)

    sink: Dict[Tuple[str, str], Finding] = {}
    _taint_jaxpr(closed.jaxpr, taints, sink, where, config)
    return list(sink.values())


def check_mask_tree(
    masks, params, *, nm: Tuple[int, int] = None, config: str = ""
) -> List[Finding]:
    """Validate a concrete mask pytree: binary values everywhere, and (when
    ``nm`` is given) exact N:M group counts along the reduction axis of
    every prunable leaf."""
    findings: List[Finding] = []

    def visit(path, leaf, mask):
        loc = "/".join(SP._path_names(path))
        m = np.asarray(mask)
        if not np.all((m == 0) | (m == 1)):
            findings.append(Finding(
                code="MSK002", severity="error", pass_name="masks",
                config=config, location=loc,
                message="mask values must be exactly {0,1}",
            ))
            return leaf
        if nm is not None and SP.is_prunable(path, leaf):
            n, mm = nm
            name = SP._path_names(path)[-1]
            mat = np.asarray(SP.to_matrix(name, jnp.asarray(m))[0])
            R = mat.shape[-2]
            if R % mm != 0:
                findings.append(Finding(
                    code="MSK004", severity="warn", pass_name="masks",
                    config=config, location=loc,
                    message=f"reduction dim {R} not divisible by M={mm}; "
                            f"N:M pattern not applicable",
                ))
                return leaf
            groups = mat.reshape(*mat.shape[:-2], R // mm, mm, mat.shape[-1]).sum(axis=-2)
            if not np.all(groups == n):
                bad = int((groups != n).sum())
                findings.append(Finding(
                    code="MSK003", severity="error", pass_name="masks",
                    config=config, location=loc,
                    message=f"{bad} group(s) violate the {n}:{mm} pattern "
                            f"(per-group kept counts range "
                            f"{int(groups.min())}..{int(groups.max())})",
                ))
        return leaf

    jax.tree_util.tree_map_with_path(visit, params, masks)
    return findings
