"""Pass 3 — jaxpr lint over traced hot paths.

Walks the jaxprs of the EBFT tune step and the serving decode step (any
jaxpr, really) and flags:

  * LNT001 (warn)  silent float widenings outside accumulators: a
    ``convert_element_type`` that widens an inexact dtype (bf16 -> f32)
    whose result feeds anything other than a contraction or reduction —
    the classic "mixed-precision model silently runs its elementwise math
    in f32 and doubles its VMEM/HBM traffic" bug;
  * LNT002 (error) host-sync points inside jit: callbacks / infeed /
    outfeed force a device->host round-trip per step and serialize the
    pipeline (ROADMAP: serve path must stay device-resident);
  * LNT003 (info)  degenerate convert round-trips A -> B -> A — the inner
    cast is lossy (if narrowing) or dead (if not), either way unintended.
"""
from __future__ import annotations

from typing import List

import jax.numpy as jnp
from jax.extend import core as jcore

from repro.analysis.findings import Finding
from repro.analysis.jaxpr_utils import iter_eqns, sub_jaxprs_of, var_consumers, var_producers

_HOST_SYNC = {"infeed", "outfeed", "debug_print"}
# consumers for which a widening convert is an accumulator idiom, not a bug
_ACCUMULATOR_CONSUMERS = {"dot_general", "conv_general_dilated", "reduce_sum",
                          "reduce_max", "reduce_min", "reduce_prod"}


def _is_float(dtype) -> bool:
    return jnp.issubdtype(dtype, jnp.inexact)


def lint_jaxpr(closed_jaxpr, where: str, config: str = "") -> List[Finding]:
    findings: List[Finding] = []
    seen = set()

    def emit(code, severity, loc, message):
        key = (code, loc, message)
        if key in seen:
            return
        seen.add(key)
        findings.append(Finding(
            code=code, severity=severity, pass_name="jaxpr",
            config=config, location=loc, message=message,
        ))

    for jaxpr, eqn in iter_eqns(closed_jaxpr):
        name = eqn.primitive.name
        if name in _HOST_SYNC or "callback" in name:
            emit(
                "LNT002", "error", f"{where}:{name}",
                f"host-sync primitive `{name}` inside a jitted hot path — "
                "forces a device->host round-trip every step",
            )

    # convert analyses need per-jaxpr producer/consumer maps
    visited = set()
    stack = [getattr(closed_jaxpr, "jaxpr", closed_jaxpr)]
    while stack:
        jaxpr = stack.pop()
        if id(jaxpr) in visited:
            continue
        visited.add(id(jaxpr))
        producers = var_producers(jaxpr)
        consumers = var_consumers(jaxpr)
        for eqn in jaxpr.eqns:
            stack.extend(sub_jaxprs_of(eqn))
            if eqn.primitive.name != "convert_element_type":
                continue
            src = eqn.invars[0]
            out = eqn.outvars[0]
            src_dt = src.aval.dtype
            out_dt = out.aval.dtype

            # LNT003: A -> B -> A round-trip
            prod = producers.get(src)
            if (
                prod is not None
                and prod.primitive.name == "convert_element_type"
                and isinstance(prod.invars[0], jcore.Var)
                and prod.invars[0].aval.dtype == out_dt
            ):
                emit(
                    "LNT003", "info", f"{where}:convert",
                    f"degenerate convert round-trip "
                    f"{out_dt.name} -> {src_dt.name} -> {out_dt.name}",
                )

            # LNT001: silent float widening outside accumulators
            if (
                _is_float(src_dt)
                and _is_float(out_dt)
                and out_dt.itemsize > src_dt.itemsize
            ):
                cons = consumers.get(out, [])
                if cons and all(
                    c.primitive.name in _ACCUMULATOR_CONSUMERS for c in cons
                ):
                    continue
                emit(
                    "LNT001", "warn", f"{where}:convert",
                    f"silent float widening {src_dt.name} -> {out_dt.name} "
                    "outside an accumulator — elementwise math runs at the "
                    "wider dtype and doubles memory traffic",
                )
    return findings
