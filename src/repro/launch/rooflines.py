"""Roofline terms from the dry-run's compiled artifact.

Hardware model: :data:`PEAKS`, keyed by ``jax.Device.device_kind``. The
dry-run models the TPU v5e target (:data:`TARGET_KIND`); measured code
looks its own device up and books no roofline figure for a kind that is
not in the table.

Terms (seconds per step, PER CHIP — the analyzer works on the partitioned
per-device program, so no extra division by chip count is needed):

    compute    = HLO_FLOPs_per_chip / peak
    memory     = HLO_bytes_per_chip / HBM_bw      (fusion-boundary proxy)
    collective = wire_bytes_per_chip / link_bw    (ring-algorithm estimate)

MODEL_FLOPS is the classic parameter-math lower bound: 6·N·D for training
(fwd + bwd), 2·N·D for inference, with N = active params for MoE. The
ratio MODEL_FLOPS/HLO_FLOPs exposes remat/redundancy waste; the roofline
fraction (useful-compute time / max term) is the headline §Perf score.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict

from repro.configs.base import ModelConfig, ShapeConfig

# Published per-chip peaks. Source: Google Cloud documentation, "TPU v5e"
# (197 TFLOP/s bf16, 819 GB/s HBM); ICI is the dry-run's ~50 GB/s per-link
# planning figure, not a published peak.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9},
}
TARGET_KIND = "TPU v5 lite"


def model_flops_per_chip(cfg: ModelConfig, shape: ShapeConfig, chips: int) -> float:
    n = cfg.param_count(active_only=(cfg.family == "moe"))
    # embedding lookups are table reads, not matmul FLOPs; the LM head IS a
    # matmul and is inside param_count. Keep the classic 6ND/2ND convention.
    # enc-dec: the encoder only sees the (seq/8)-long frame stream, so its
    # params process 8x fewer tokens than the decoder's.
    embed = cfg.padded_vocab * cfg.d_model * (1 if cfg.tie_embeddings else 2)
    if cfg.family == "encdec":
        dec_frac = cfg.num_layers / max(cfg.num_layers + cfg.enc_layers, 1)
        n = (n - embed) * (dec_frac + (1 - dec_frac) / 8.0) + embed
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens / chips
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens / chips
    if shape.kind == "ebft":
        # one block's fwd+bwd over the calibration batch (no optimizer/embed)
        n_layers = cfg.num_layers + (cfg.enc_layers or 0)
        n_block = (n - embed) / max(n_layers, 1)
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_block * tokens / chips
    # decode: one token per sequence per step
    return 2.0 * n * shape.global_batch / chips


@dataclasses.dataclass
class Roofline:
    compute_s: float
    memory_s: float
    collective_s: float
    bottleneck: str
    model_flops_per_chip: float
    hlo_flops_per_chip: float
    useful_ratio: float       # MODEL_FLOPS / HLO_FLOPs
    roofline_fraction: float  # useful-compute time / max(terms)

    def asdict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


def terms(
    stats: Any,  # HLOStats
    cfg: ModelConfig,
    shape: ShapeConfig,
    chips: int,
) -> Roofline:
    peak = PEAKS[TARGET_KIND]
    compute_s = stats.flops / peak["flops"]
    memory_s = stats.hbm_bytes / peak["hbm_bw"]
    collective_s = stats.collective_wire / peak["ici_bw"]
    names = ("compute", "memory", "collective")
    vals = (compute_s, memory_s, collective_s)
    bottleneck = names[max(range(3), key=lambda i: vals[i])]
    mf = model_flops_per_chip(cfg, shape, chips)
    bound = max(max(vals), 1e-30)
    return Roofline(
        compute_s=compute_s,
        memory_s=memory_s,
        collective_s=collective_s,
        bottleneck=bottleneck,
        model_flops_per_chip=mf,
        hlo_flops_per_chip=stats.flops,
        useful_ratio=mf / max(stats.flops, 1e-30),
        roofline_fraction=(mf / peak["flops"]) / bound,
    )
