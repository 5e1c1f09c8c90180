"""Launch-path integration test: lower+compile train & decode steps on a
small (2,4) mesh in a subprocess (8 virtual devices), including the HLO
roofline analysis — the same code path dryrun.py uses on the 512-chip mesh."""
import subprocess
import sys

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.configs import get_config
from repro.launch.mesh import build_rules, make_test_mesh, to_shardings
from repro.launch import specs as S
from repro.launch.hlo_analysis import analyze
from repro.models.config import ShapeCell
from repro.models.layers import set_logical_rules
from repro.models import transformer as T
from repro.launch.serve import make_serve_step
from repro.train import optimizer as opt
from repro.train.train_loop import make_train_step

cfg = get_config("granite-3-2b").smoke_config()
mesh = make_test_mesh((2, 4), ("data", "model"))
rules = build_rules({"heads": None, "kv_heads": None}, batch_size=8,
                    dp_degree=2)
set_logical_rules(rules)

# --- train step
cell = ShapeCell("tiny_train", 64, 8, "train")
fn, args, insh, outsh = S.train_cell_specs(cfg, cell, rules, False)
with jax.set_mesh(mesh):
    compiled = jax.jit(fn, in_shardings=to_shardings(mesh, insh),
                       out_shardings=to_shardings(mesh, outsh),
                       donate_argnums=(0, 1)).lower(*args).compile()
    mem = compiled.memory_analysis()
r = analyze(compiled.as_text())
assert r["flops"] > 0
assert r["hbm_bytes"] > 0
assert mem.temp_size_in_bytes > 0
print("train ok: flops", r["flops"])

# --- decode step
cell = ShapeCell("tiny_decode", 64, 8, "decode")
fn, args, insh, outsh = S.decode_cell_specs(cfg, cell, rules)
with jax.set_mesh(mesh):
    compiled = jax.jit(fn, in_shardings=to_shardings(mesh, insh),
                       out_shardings=to_shardings(mesh, outsh),
                       donate_argnums=(2,)).lower(*args).compile()
r = analyze(compiled.as_text())
assert r["flops"] > 0
print("decode ok")
print("OK")
"""


def test_small_mesh_dryrun_path():
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, (r.stdout[-1500:], r.stderr[-3000:])
    assert "OK" in r.stdout
