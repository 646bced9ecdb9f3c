"""Tiny cells for the CPU tests: the real harness, configurations and
traffic at widths a test run holds, written into a copy of the
benchmark under a temporary root."""
from __future__ import annotations

import json
import os
import shutil
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

CORPUS = {"seed": 0}

TINY_QWEN = {
    "name": "tiny_qwen", "source": "test", "hidden_size": 64, "intermediate_size": 128,
    "num_attention_heads": 4, "num_key_value_heads": 2, "num_hidden_layers": 2,
    "vocab_size": 512, "rms_norm_eps": 1e-06, "rope_theta": 1000000.0,
    "reference": "ref_qwen1_5.py", "registered": "qwen1_5_4b",
    "model_config": {"num_layers": 2, "d_model": 64, "num_heads": 4, "num_kv_heads": 2,
                     "d_ff": 128, "vocab_size": 512},
    "agrees": {"d_model": "hidden_size", "d_ff": "intermediate_size",
               "num_heads": "num_attention_heads", "num_kv_heads": "num_key_value_heads",
               "num_layers": "num_hidden_layers", "vocab_size": "vocab_size",
               "rope_theta": "rope_theta"},
    "program_fields": {"qkv_bias": True, "family": "dense", "dtype": "float32"},
}

TINY_MAMBA = {
    "name": "tiny_mamba", "source": "test", "d_model": 64, "n_layer": 2, "vocab_size": 500,
    "ssm_cfg": {"layer": "Mamba2", "d_state": 16, "d_conv": 4, "expand": 2,
                "headdim": 16, "ngroups": 1, "chunk_size": 16},
    "pad_vocab_size_multiple": 16,
    "reference": "ref_mamba2.py", "registered": "mamba2_130m",
    "model_config": {"num_layers": 2, "d_model": 64, "vocab_size": 500,
                     "vocab_pad_multiple": 16, "ssm_state": 16, "ssm_head_dim": 16,
                     "ssm_chunk": 16},
    "agrees": {"d_model": "d_model", "num_layers": "n_layer", "vocab_size": "vocab_size",
               "ssm_state": "ssm_cfg.d_state", "ssm_head_dim": "ssm_cfg.headdim",
               "ssm_chunk": "ssm_cfg.chunk_size"},
    "program_fields": {"family": "ssm", "dtype": "float32"},
}

# the program's tiny_moe (DeepSeek-MoE's layout at test widths: one dense
# block, then two MoE blocks of 8 routed experts, top-2, 2 shared); its
# reference (ref_moe.py, beside this file) is dropless, and a capacity
# factor of n_routed_experts / num_experts_per_tok makes each expert's
# capacity the whole token group, so the program drops no token either
TINY_MOE = {
    "name": "tiny_moe", "source": "test", "hidden_size": 64, "intermediate_size": 256,
    "moe_intermediate_size": 32, "num_attention_heads": 4, "num_key_value_heads": 4,
    "num_hidden_layers": 3, "first_k_dense_replace": 1, "n_routed_experts": 8,
    "num_experts_per_tok": 2, "n_shared_experts": 2, "vocab_size": 512,
    "rms_norm_eps": 1e-06, "rope_theta": 10000.0,
    "reference": "ref_moe.py", "registered": "tiny_moe",
    "model_config": {"moe_capacity_factor": 4.0},
    "agrees": {"d_model": "hidden_size", "d_ff": "intermediate_size",
               "moe_d_ff": "moe_intermediate_size", "num_heads": "num_attention_heads",
               "num_kv_heads": "num_key_value_heads", "num_layers": "num_hidden_layers",
               "moe_first_dense": "first_k_dense_replace",
               "moe_num_experts": "n_routed_experts", "moe_top_k": "num_experts_per_tok",
               "moe_num_shared": "n_shared_experts", "vocab_size": "vocab_size",
               "rope_theta": "rope_theta"},
    "program_fields": {"family": "moe", "qkv_bias": False, "mlp_act": "swiglu",
                       "norm": "rmsnorm", "tie_embeddings": False, "dtype": "float32",
                       "moe_capacity_factor": 4.0, "moe_dispatch_groups": 1},
}

TINY_WALK = {
    "driver": "walk", "corpus": CORPUS,
    "calibration": {"samples": 16, "seq_len": 32},
    "prune": {"method": "wanda", "sparsity": 0.7, "samples": 16, "seq_len": 32},
    "ebft": {"lr": 0.0002, "epochs": 3, "microbatch": 8, "patience": 2, "rel_tol": 0.001},
    "window": {"calibration_sets": 2},
}

TINY_SERVE = {
    "driver": "serve_single", "corpus": CORPUS, "slots": 1, "max_len": 48, "cycles": 2,
    "cycle": {"prompt_lengths": [8, 16], "prompt_counts": [2, 1],
              "output": {"median": 16, "sigma": 0.3, "min": 4, "max": 24}},
    "prune": {"method": "wanda", "sparsity": 0.5, "samples": 8, "seq_len": 16,
              "microbatch": 8},
    "check_requests": 6,
}

# At these widths on the CPU the program computes in full float32, so it
# agrees with the reference to rounding. The walk cells keep the chip
# cells' own limits, which a sound program here passes by orders of
# magnitude. The tiny serving cell states no matmul precision, so its
# control is the program's bfloat16 path, and it has a logit limit of its
# own: the sound program reads about 1e-6 here, the control 1e-3 or more.
WALK_LIMITS = os.path.join(BENCH, "limits", "qwen4b-walk.json")
SERVE_LIMITS = {"limits": {"logit_err": 1e-4, "widest_gap": 0.05, "replay": 0, "bad_ids": 0}}

CELLS = {
    "tiny-walk": ("tiny_qwen", TINY_QWEN, "tiny_walk", TINY_WALK, WALK_LIMITS),
    "tiny-mamba-walk": ("tiny_mamba", TINY_MAMBA, "tiny_walk", TINY_WALK, WALK_LIMITS),
    "tiny-serve": ("tiny_qwen", TINY_QWEN, "tiny_serve", TINY_SERVE, SERVE_LIMITS),
    "tiny-moe-walk": ("tiny_moe", TINY_MOE, "tiny_walk", TINY_WALK, WALK_LIMITS),
    "tiny-moe-serve": ("tiny_moe", TINY_MOE, "tiny_serve", TINY_SERVE, SERVE_LIMITS),
}
# references kept with the tests, which make_root adds as new files
TEST_REFERENCES = ("ref_moe.py",)


def reference(conf):
    """The plain reference module a configuration names."""
    from harness import spec as S

    name = conf["reference"]
    where = "tests" if name in TEST_REFERENCES else "configs"
    return S.load_module(os.path.join(BENCH, where, name), f"bench_tiny_{conf['name']}")


def make_root(tmp: str) -> str:
    """A checkout-like root under ``tmp``: BENCHMARK.json and bench/ copied,
    the tiny configurations, their test references, traffic, limits and
    cells added; no file that bench/ has is changed."""
    root = os.path.join(tmp, "root")
    shutil.copytree(BENCH, os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    for name in TEST_REFERENCES:
        shutil.copy(os.path.join(BENCH, "tests", name), os.path.join(root, "bench", "configs"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    for cell, (cname, conf, tname, traffic, limits) in CELLS.items():
        path = f"bench/configs/{cname}.json"
        with open(os.path.join(root, path), "w") as f:
            json.dump(conf, f)
        with open(os.path.join(root, "bench", "traffic", f"{tname}.json"), "w") as f:
            json.dump(traffic, f)
        dst = os.path.join(root, "bench", "limits", f"{cell}.json")
        if isinstance(limits, dict):
            with open(dst, "w") as f:
                json.dump(limits, f)
        else:
            shutil.copy(limits, dst)
        if cname not in {c["name"] for c in spec["configs"]}:
            spec["configs"].append({"name": cname, "source": "test", "file": path,
                                    "reduced": [], "why": "test"})
        spec["workloads"].append({"name": cell, "config": cname, "traffic": tname,
                                  "chips": 1, "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            kind = "walk" if any("walk" in w for w in m["workloads"]) else "serve"
            m["workloads"] += [c for c in CELLS if kind in c]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return root


def cell(root: str, name: str):
    from harness import spec as S

    return S.cell(S.load_spec(root), name, root)


# -- the check, driven through whole runs (test_bench_faults_*.py) -------
SEED = 2**31 + 99


def run(root: str, name: str, plant=None):
    """A whole run of a tiny cell, the chip look skipped."""
    import jax

    from harness import cli

    return cli.run_cell(cell(root, name), SEED, 0.5, False, 0.0, jax.devices(),
                        plant=plant)


def fault_names(name: str):
    from harness import faults

    return list(faults.FAULTS[CELLS[name][3]["driver"]])


def check_cell(root: str, name: str, fault: str):
    """``fault`` is "none" (the sound program must be correct), "control"
    (the bfloat16 path must not be), or a planted fault (must not be)."""
    import jax

    import calibrate
    from harness import faults

    c = cell(root, name)
    if fault == "none":
        res = run(root, name)
        assert res["correct"], res["check"]
        assert res["attempted"] >= 1 and res["failed"] == 0
    elif fault == "control":
        nums, _ = calibrate.reading(c, SEED + 1, 0.5, jax.devices(), control_run=True)
        assert any(nums[k] > v for k, v in c.limits.items()), nums
    else:
        res = run(root, name, plant=faults.FAULTS[c.traffic["driver"]][fault])
        assert not res["correct"], res["check"]
