"""Distribution integration: lower+compile a sharded train/decode step on a
multi-device mesh. Needs >1 device, so it runs in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (the main test process
keeps the default single device, per the assignment)."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.launch import steps as ST
from repro.launch import hlo_analysis as HA

cfg = get_config("tiny_dense").replace(num_layers=2)
from repro.launch.mesh import make_debug_mesh
mesh = make_debug_mesh(2, 4)
out = {}

# train cell
shape = ShapeConfig("t", 64, 8, "train")
cell = ST.build_train_cell(cfg, shape, mesh, microbatches=2, fsdp=False)
with mesh:
    compiled = ST.lower_cell(cell).compile()
ma = compiled.memory_analysis()
st = HA.analyze(compiled.as_text(), 8)
out["train"] = {
    "temp_bytes": ma.temp_size_in_bytes,
    "flops": st.flops,
    "collective_wire": st.collective_wire,
}

# run the compiled step with real (tiny) buffers to prove executability
params = jax.device_put(cell.model.init(jax.random.PRNGKey(0)), cell.in_shardings[0])
from repro.optim.optimizers import adamw
opt = adamw(1e-4)
opt_state = jax.device_put(opt.init(params), cell.in_shardings[1])
batch = jax.device_put(
    {"tokens": jnp.ones((8, 64), jnp.int32)}, cell.in_shardings[2]
)
jitted = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                 out_shardings=cell.out_shardings,
                 donate_argnums=cell.donate_argnums)
with mesh:
    p2, o2, metrics = jitted(params, opt_state, batch)
out["train"]["loss_finite"] = bool(jnp.isfinite(metrics["loss"]))

# decode cell
shape_d = ShapeConfig("d", 256, 8, "decode")
cell_d = ST.build_decode_cell(cfg, shape_d, mesh)
with mesh:
    compiled_d = ST.lower_cell(cell_d).compile()
out["decode"] = {"ok": True}
print("RESULT " + json.dumps(out))
"""


@pytest.mark.slow
def test_sharded_train_and_decode_compile_and_run():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT], env=env, capture_output=True,
        text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")][-1]
    out = json.loads(line[len("RESULT "):])
    assert out["train"]["loss_finite"]
    assert out["train"]["flops"] > 0
    assert out["train"]["collective_wire"] > 0  # grad all-reduce exists
    assert out["decode"]["ok"]


@pytest.mark.slow
def test_dryrun_cell_json_schema(tmp_path):
    """Run the actual dryrun module for one small cell (8 devices) and
    validate the JSON record schema the roofline reader consumes."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, json
from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.launch import steps as ST, hlo_analysis as HA, rooflines as RL

cfg = get_config("tiny_ssm")
from repro.launch.mesh import make_debug_mesh
mesh = make_debug_mesh(4, 2)
shape = ShapeConfig("t", 64, 8, "train")
cell = ST.build_train_cell(cfg, shape, mesh, microbatches=1, fsdp=False)
with mesh:
    compiled = ST.lower_cell(cell).compile()
st = HA.analyze(compiled.as_text(), 8)
roof = RL.terms(st, cell.cfg, shape, 8)
rec = {"hlo_stats": st.asdict(), "roofline": roof.asdict()}
print("RESULT " + json.dumps(rec))
"""
    proc = subprocess.run(
        [sys.executable, "-c", script], env=env, capture_output=True,
        text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")][-1]
    rec = json.loads(line[len("RESULT "):])
    roof = rec["roofline"]
    for key in ("compute_s", "memory_s", "collective_s", "bottleneck",
                "model_flops_per_chip", "hlo_flops_per_chip",
                "useful_ratio", "roofline_fraction"):
        assert key in roof
    assert roof["bottleneck"] in ("compute", "memory", "collective")
    assert rec["hlo_stats"]["flops"] > 0
