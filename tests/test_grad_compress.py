"""int8 compressed psum vs exact psum (8-device subprocess not needed:
shard_map over a 1-device mesh still exercises the code path; the
multi-device semantics run in test_distributed.py)."""
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.train.grad_compress import compressed_psum, compressed_psum_with_feedback

mesh = jax.make_mesh((8,), ("data",))
x = jax.random.normal(jax.random.PRNGKey(0), (8, 128)) * 3.0

exact = jnp.sum(x, axis=0)
f = shard_map(lambda xs: compressed_psum(xs[0], "data"), mesh=mesh,
              in_specs=P("data"), out_specs=P())
got = f(x)
rel = float(jnp.max(jnp.abs(got - exact)) / jnp.max(jnp.abs(exact)))
assert rel < 0.05, f"one-shot int8 psum rel err {rel}"

# error feedback: averaged over steps, bias vanishes
err = jnp.zeros((8, 128))
acc_exact = jnp.zeros(128)
acc_comp = jnp.zeros(128)
def step(key, err):
    g = jax.random.normal(key, (8, 128))
    def body(gs, es):
        red, ne = compressed_psum_with_feedback(gs[0], es[0], "data")
        return red, ne[None]                     # residual stays per-shard
    f2 = shard_map(body, mesh=mesh, in_specs=(P("data"), P("data")),
                   out_specs=(P(), P("data")))
    red, new_err = f2(g, err)
    return g.sum(0), red, new_err
key = jax.random.PRNGKey(1)
for i in range(30):
    key, k = jax.random.split(key)
    ex, red, err = step(k, err)
    acc_exact += ex
    acc_comp += red
rel = float(jnp.linalg.norm(acc_comp - acc_exact) / jnp.linalg.norm(acc_exact))
assert rel < 0.05, f"error-feedback accumulated rel err {rel}"
# and error feedback must beat naive compression accumulated over steps
acc_naive = jnp.zeros(128)
key = jax.random.PRNGKey(1)
f1 = shard_map(lambda gs: compressed_psum(gs[0], "data"), mesh=mesh,
               in_specs=P("data"), out_specs=P())
for i in range(30):
    key, k = jax.random.split(key)
    acc_naive += f1(jax.random.normal(k, (8, 128)))
rel_naive = float(jnp.linalg.norm(acc_naive - acc_exact) / jnp.linalg.norm(acc_exact))
assert rel < rel_naive, (rel, rel_naive)
print("OK")
"""


def test_compressed_psum_multidevice():
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout


def test_quantize_roundtrip_bounds():
    from repro.train.grad_compress import quantize
    x = jnp.linspace(-5, 5, 100)
    scale = jnp.float32(5 / 127.0)
    q = quantize(x, scale)
    back = q.astype(jnp.float32) * scale
    assert float(jnp.max(jnp.abs(back - x))) <= float(scale) / 2 + 1e-6
