"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state — the dry-run must set
XLA_FLAGS before any jax initialization.

Single pod: (16, 16) = 256 chips, axes ("data", "model").
Multi-pod:  (2, 16, 16) = 512 chips, axes ("pod", "data", "model") — the
"pod" axis composes with "data" for batch/gradient parallelism (the
cross-pod all-reduce rides DCN) and with FSDP param sharding for the
trillion-param configs.
"""
from __future__ import annotations

import jax
from jax.sharding import AbstractMesh, AxisType


def _auto(n: int):
    # every axis Auto: GSPMD propagates shardings and the model code's
    # plain gathers/constraints stay legal (make_mesh defaults to Explicit)
    return (AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_debug_mesh(data: int = 1, model: int = 1):
    """Small (data, model) mesh over the first ``data * model`` devices."""
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=_auto(2))


def make_ebft_plan(data: int = 0, model: int = 1):
    """MeshPlan for the EBFT calibration walk (docs/DISTRIBUTED.md).

    ``data=0`` sizes the data axis to use every device not taken by the
    model axis; ``data=1, model=1`` (the CLI default) returns the inactive
    single-device plan, keeping the non-mesh path bit-for-bit unchanged.
    On CPU the 8-fake-device repro is::

        XLA_FLAGS=--xla_force_host_platform_device_count=8 \
            python -m repro.launch.ebft_run --mesh-data 4 --mesh-model 2 ...
    """
    from repro.distributed.meshplan import MeshPlan

    ndev = jax.device_count()
    model = max(int(model), 1)
    if data == 0:
        data = max(ndev // model, 1)
    data = max(int(data), 1)
    if data * model == 1:
        return MeshPlan.single()
    if data * model > ndev:
        raise ValueError(
            f"mesh ({data} data x {model} model) needs {data * model} "
            f"devices but only {ndev} exist — set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={data * model} for a "
            "CPU repro, or shrink the axes"
        )
    return MeshPlan.from_mesh(make_debug_mesh(data, model))


def make_abstract_mesh(shape, axis_names):
    """Device-free mesh for sharding-rule checks (tests, repro.analysis)."""
    return AbstractMesh(tuple(shape), tuple(axis_names),
                        axis_types=_auto(len(axis_names)))


def abstract_production_mesh(*, multi_pod: bool = False):
    """AbstractMesh twin of ``make_production_mesh`` (no devices needed)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_abstract_mesh(shape, axes)
