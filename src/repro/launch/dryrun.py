import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: prove every (architecture x input-shape x mesh) cell
lowers, SPMD-partitions, and compiles for the production meshes, and
extract the roofline terms from the compiled artifact.

The two lines ABOVE the docstring are load-bearing: jax locks the device
count at first initialization, so the 512 placeholder CPU devices must be
requested before ANY jax import (including transitive ones).

Usage:
    python -m repro.launch.dryrun                       # full 40-cell sweep, both meshes
    python -m repro.launch.dryrun --arch qwen1_5_4b --shape train_4k --mesh single
    python -m repro.launch.dryrun --tag fsdp_off --fsdp off ...   # perf variants

Each cell writes experiments/dryrun/<tag>/<arch>__<shape>__<mesh>.json with:
    memory_analysis   (per-device argument/output/temp bytes)
    cost_analysis     (XLA's flops/bytes — understates scanned loops; kept
                       for reference)
    hlo_stats         (trip-count-weighted FLOPs / HBM-proxy bytes /
                       collective wire bytes — see launch/hlo_analysis.py)
    roofline          (three terms, bottleneck, useful ratio, fraction)
"""
import json
import time
import traceback
from typing import Optional

import jax

from repro.configs import get_config, list_configs
from repro.launch import hlo_analysis as HA
from repro.launch import rooflines as RL
from repro.launch import steps as ST
from repro.launch.mesh import make_production_mesh
from repro.obs import metrics as OM
from repro.obs import trace as OT


def run_cell(
    arch: str,
    shape_name: str,
    mesh_name: str,
    out_dir: str,
    fsdp: Optional[bool] = None,
    microbatches: Optional[int] = None,
    skip_existing: bool = False,
    assume_flash: bool = False,
    ebft_dp: bool = False,
) -> dict:
    path = os.path.join(out_dir, f"{arch}__{shape_name}__{mesh_name}.json")
    if skip_existing and os.path.exists(path):
        with open(path) as f:
            rec = json.load(f)
        if "error" not in rec:
            print(f"[skip] {arch} {shape_name} {mesh_name} (cached)")
            return rec

    cfg = get_config(arch)
    if shape_name == "ebft_block":
        shape = ST.EBFT_SHAPE  # the paper's own workload (Alg. 1 inner step)
    else:
        shape = next(s for s in cfg.shapes() if s.name == shape_name)
    mesh = make_production_mesh(multi_pod=(mesh_name == "multi"))
    chips = mesh.devices.size
    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name, "chips": chips,
        "kind": shape.kind,
    }
    t0 = time.perf_counter()
    try:
        if shape.kind == "train":
            cell = ST.build_cell(cfg, shape, mesh, fsdp=fsdp, microbatches=microbatches)
        elif shape.kind == "ebft":
            cell = ST.build_ebft_cell(cfg, shape, mesh, dp_only=ebft_dp)
        else:
            cell = ST.build_cell(cfg, shape, mesh)
        with mesh, OT.span("dryrun/cell", arch=arch, shape=shape_name,
                           mesh=mesh_name):
            lowered = ST.lower_cell(cell)
            t_lower = time.perf_counter() - t0
            compiled = lowered.compile()
            t_compile = time.perf_counter() - t0 - t_lower
        if OT.enabled():
            OM.gauge(f"dryrun/{arch}__{shape_name}__{mesh_name}/lower_s").set(t_lower)
            OM.gauge(
                f"dryrun/{arch}__{shape_name}__{mesh_name}/compile_s"
            ).set(t_compile)

        ma = compiled.memory_analysis()
        rec["memory_analysis"] = {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "peak_bytes_est": ma.argument_size_in_bytes + ma.temp_size_in_bytes,
        }
        ca = compiled.cost_analysis() or {}
        rec["cost_analysis"] = {
            "flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        }
        vmem = None
        if assume_flash:
            c = cell.cfg
            qc = c.attn_q_chunk or shape.seq_len
            vmem = {(qc, c.attn_chunk), (c.attn_chunk, c.attn_chunk),
                    (qc, qc), (1, c.attn_chunk)}
            rec["assume_flash"] = True
        stats = HA.analyze(compiled.as_text(), chips, vmem_score_shapes=vmem)
        rec["hlo_stats"] = stats.asdict()
        roof = RL.terms(stats, cell.cfg, shape, chips)
        rec["roofline"] = roof.asdict()
        rec["timing"] = {"lower_s": t_lower, "compile_s": t_compile}
        rec["fsdp"] = bool(ST.wants_fsdp(cell.cfg)) if fsdp is None else fsdp
        print(
            f"[ok]   {arch:24s} {shape_name:12s} {mesh_name:6s} "
            f"comp={roof.compute_s*1e3:9.2f}ms mem={roof.memory_s*1e3:9.2f}ms "
            f"coll={roof.collective_s*1e3:9.2f}ms -> {roof.bottleneck:10s} "
            f"frac={roof.roofline_fraction:.3f} "
            f"hbm/dev={rec['memory_analysis']['peak_bytes_est']/2**30:.1f}GiB "
            f"(compile {t_compile:.0f}s)",
            flush=True,
        )
    except Exception as e:  # noqa: BLE001 — record and continue the sweep
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
        print(f"[FAIL] {arch} {shape_name} {mesh_name}: {rec['error']}", flush=True)

    os.makedirs(out_dir, exist_ok=True)
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main(argv=None) -> None:
    from repro.launch.api import RunSpec
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    spec = RunSpec.from_argv("dryrun", argv)
    archs = list_configs() if spec.arch == "all" else spec.arch.split(",")
    meshes = ["single", "multi"] if spec.mesh == "both" else [spec.mesh]
    fsdp = None if spec.fsdp == "auto" else (spec.fsdp == "on")
    mb = spec.microbatches or None
    out_dir = os.path.join(spec.out, spec.tag)

    failures = 0
    for arch in archs:
        cfg = get_config(arch)
        shape_names = (
            [s.name for s in cfg.shapes()] if spec.shape == "all"
            else spec.shape.split(",")
        )
        for shape_name in shape_names:
            for mesh_name in meshes:
                rec = run_cell(
                    arch, shape_name, mesh_name, out_dir,
                    fsdp=fsdp, microbatches=mb,
                    skip_existing=spec.skip_existing,
                    assume_flash=spec.assume_flash,
                    ebft_dp=spec.ebft_dp,
                )
                failures += int("error" in rec)
    print(f"\ndry-run complete; failures={failures}")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
