"""Small helpers for walking jaxprs recursively.

Control-flow and call primitives carry sub-jaxprs in their params under a
handful of conventional keys; ``iter_eqns`` yields every equation in a
closed jaxpr including those nested inside ``pjit``/``scan``/``while``/
``cond``/``remat``/``custom_*`` bodies, together with the jaxpr that owns
it (so per-jaxpr producer maps stay consistent).
"""
from __future__ import annotations

from typing import Any, Iterator, List, Tuple

from jax.extend import core as jcore


def _as_jaxpr(obj) -> Any:
    """ClosedJaxpr -> Jaxpr; Jaxpr passes through."""
    return getattr(obj, "jaxpr", obj)


def sub_jaxprs_of(eqn) -> List[Any]:
    """All sub-jaxprs (as plain Jaxprs) referenced by an equation."""
    out: List[Any] = []
    for key in ("jaxpr", "call_jaxpr", "fun_jaxpr", "cond_jaxpr", "body_jaxpr"):
        sub = eqn.params.get(key)
        if sub is not None:
            out.append(_as_jaxpr(sub))
    for br in eqn.params.get("branches", ()) or ():
        out.append(_as_jaxpr(br))
    return out


def iter_eqns(closed_jaxpr) -> Iterator[Tuple[Any, Any]]:
    """Yield ``(owning_jaxpr, eqn)`` for every equation, depth-first."""
    stack = [_as_jaxpr(closed_jaxpr)]
    seen = set()
    while stack:
        jaxpr = stack.pop()
        if id(jaxpr) in seen:
            continue
        seen.add(id(jaxpr))
        for eqn in jaxpr.eqns:
            yield jaxpr, eqn
            stack.extend(sub_jaxprs_of(eqn))


def var_producers(jaxpr) -> dict:
    """Map each Var to the eqn that produces it (within one jaxpr).

    Dropped outputs are ``Var`` subclasses that no equation reads, so
    their entries are never looked up and need no filtering."""
    return {v: eqn for eqn in jaxpr.eqns for v in eqn.outvars}


def var_consumers(jaxpr) -> dict:
    """Map each Var to the eqns that consume it (within one jaxpr)."""
    cons: dict = {}
    for eqn in jaxpr.eqns:
        for v in eqn.invars:
            if isinstance(v, jcore.Var):
                cons.setdefault(v, []).append(eqn)
    return cons
