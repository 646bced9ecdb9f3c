"""The chip look and the peaks table.

A run on anything but a TPU, or on fewer chips than the cell asks for,
stops before any result is printed: a CPU number is never a device
number. A TPU whose ``device_kind`` is not in :data:`PEAKS` stops too,
since every share of a peak would then be against a guessed peak.
"""
from __future__ import annotations

from typing import Any, Dict

# Published per-chip peaks, keyed by jax.Device.device_kind.
# Source: Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e):
# 197 TFLOP/s bf16 (the MXU rate that a float32 matmul at JAX's default
# precision runs at: one bf16 pass with f32 accumulation), 819 GB/s HBM,
# 16 GB of HBM.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


class NoChip(Exception):
    """JAX found no TPU, too few chips, or a chip of unknown kind."""


def peaks(device_kind: str) -> Dict[str, float]:
    if device_kind not in PEAKS:
        raise NoChip(f"no peaks for device kind {device_kind!r}; known: "
                     f"{sorted(PEAKS)}")
    return PEAKS[device_kind]


def look(chips: int):
    """Returns the devices of the cell, or raises :class:`NoChip`."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform if devices else "none"
    if platform != "tpu":
        raise NoChip(f"the benchmark needs a TPU; JAX found {platform!r}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX sees {len(devices)}")
    peaks(devices[0].device_kind)
    return devices[:chips]


def describe(devices) -> Dict[str, Any]:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest chip, where the backend reports it."""
    peaks_ = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks_.append(int(stats["peak_bytes_in_use"]))
    return max(peaks_) if peaks_ else 0
