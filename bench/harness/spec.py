"""Finds everything a cell needs by the names ``BENCHMARK.json`` gives.

    <root>/BENCHMARK.json               the cells, metrics and bounds
    <root>/bench/configs/<file>         a configuration (the file that
                                        BENCHMARK.json names), with its plain
                                        reference module beside it
    <root>/bench/traffic/<traffic>.json a traffic mix; its "driver" names
    <root>/bench/drivers/<driver>.py    the code that runs such a mix
    <root>/bench/limits/<cell>.json     the limits of the cell's comparisons
    <root>/bench/metrics/<metric>.py    one reader per per-layer metric

A new configuration, traffic mix, cell or per-layer metric is a new file
and a new entry in BENCHMARK.json; no existing file needs an edit.

The harness names no block key and no model family: what it needs of a
model comes from the reference module that the configuration names
(``"reference"``), which defines

    BLOCK_KEYS          the top-level keys of the program's parameter tree
                        that hold the blocks, in visit order, each a stack
                        of blocks on the leaves' leading axis (layout.py)
    PRUNABLE            leaf path below a block -> (tap, input axes) or
                        (tap, input axes, "experts"): the linear layers
                        Wanda prunes, scored on the block's tap of that
                        name; "experts": the leaf's leading axis and the
                        tap's index experts (wanda.py)
    init(key, conf)     seeded float32 weights in the program's layout
    embed(params, tokens)
    block(bp, h, conf, taps=False, precision=HIGHEST)
                        one block on h (B, S, d); with ``taps`` also its
                        taps, (B, S, inputs) or (experts, B, S, inputs)
    forward(params, tokens, conf)
                        logits over the real vocabulary
    num_blocks(conf), hidden_size(conf)
    block_matmul_params(conf, i), block_params(conf, i),
    block_fwd_flops_per_token(conf, i, seq), kv_bytes_per_position(conf, i)
                        block i's work counts (flops.py composes them)
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Any, Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


class SpecError(Exception):
    """A name in BENCHMARK.json that resolves to no file, or a file that
    does not say what the harness needs."""


def load_json(path: str) -> Dict[str, Any]:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError as e:
        raise SpecError(f"missing file {path}") from e


def load_module(path: str, name: str):
    """Imports the Python file at ``path`` under module name ``name``."""
    if not os.path.isfile(path):
        raise SpecError(f"missing file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    root: str
    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]      # the configuration file
    traffic_name: str
    traffic: Dict[str, Any]     # the traffic file
    limits: Dict[str, float]    # check name -> limit
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]

    @property
    def bench_dir(self) -> str:
        return os.path.join(self.root, "bench")

    def reference(self):
        """The configuration's plain reference module."""
        path = os.path.join(self.bench_dir, "configs", self.config["reference"])
        return load_module(path, f"bench_ref_{self.config_name}")

    def driver(self):
        path = os.path.join(self.bench_dir, "drivers",
                            f"{self.traffic['driver']}.py")
        return load_module(path, f"bench_driver_{self.traffic['driver']}")

    def metric_reader(self, name: str):
        path = os.path.join(self.bench_dir, "metrics", f"{name}.py")
        return load_module(path, f"bench_metric_{name}")


def load_spec(root: str = ROOT) -> Dict[str, Any]:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def cell(spec: Dict[str, Any], workload: str, root: str = ROOT) -> Cell:
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SpecError(f"no workload {workload!r} in BENCHMARK.json "
                        f"(have {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"workload {workload!r} names config "
                        f"{w['config']!r}, which BENCHMARK.json lacks")
    config = load_json(os.path.join(root, configs[w["config"]]["file"]))
    bench = os.path.join(root, "bench")
    traffic = load_json(os.path.join(bench, "traffic", f"{w['traffic']}.json"))
    limits = load_json(os.path.join(bench, "limits", f"{workload}.json"))

    def here(m):
        return "workloads" not in m or workload in m["workloads"]

    e2e = [m for m in spec["end_to_end"] if here(m)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if workload in m.get("workloads", ())
                 or ("workloads" not in m and m["moves"] in e2e_names)]
    return Cell(root=root, name=workload, chips=int(w["chips"]),
                config_name=w["config"], config=config,
                traffic_name=w["traffic"], traffic=traffic,
                limits={k: float(v) for k, v in limits["limits"].items()},
                end_to_end=e2e, per_layer=per_layer)
