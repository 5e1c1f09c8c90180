"""Pipeline parallelism: 2-stage pipelined loss/grads must match the plain
(non-pipelined) model exactly (subprocess: 2 virtual devices)."""
import subprocess
import sys

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.models import transformer as T
from repro.train.pipeline import (make_pipelined_loss, stack_stage_params,
                                  pipelined_loss_and_grad)

cfg = get_config("granite-3-2b").smoke_config().replace(
    compute_dtype="float32", remat="none")
params = T.init_params(jax.random.PRNGKey(0), cfg)
M, mb, S = 4, 2, 16
k = jax.random.PRNGKey(1)
tokens = jax.random.randint(k, (M, mb, S), 0, cfg.vocab_size)
labels = jax.random.randint(jax.random.fold_in(k, 1), (M, mb, S), 0,
                            cfg.vocab_size)

# reference: plain per-microbatch loss, averaged
ref_losses = [float(T.loss_fn(params, {"tokens": tokens[i],
                                       "labels": labels[i]}, cfg))
              for i in range(M)]
ref = float(np.mean(ref_losses))

mesh = jax.make_mesh((2,), ("pod",))
sp = stack_stage_params(params, cfg, n_stages=2)
with jax.set_mesh(mesh):
    fn = make_pipelined_loss(cfg, mesh, n_stages=2)
    got = float(jax.jit(fn)(sp, tokens, labels))
assert abs(got - ref) / abs(ref) < 1e-5, (got, ref)

# gradient check: pipelined grads of the group params match sequential grads
def ref_loss_fn(p):
    return sum(T.loss_fn(p, {"tokens": tokens[i], "labels": labels[i]}, cfg)
               for i in range(M)) / M
ref_grads = jax.grad(ref_loss_fn)(params)
with jax.set_mesh(mesh):
    _, pipe_grads = pipelined_loss_and_grad(cfg, mesh, sp, tokens, labels)
# compare one representative group-leaf: reassemble stage halves
pg = np.asarray(pipe_grads["groups"]["pos0_attn"]["wq"])   # (2, G/2, ...)
rg = np.asarray(ref_grads["groups"]["pos0_attn"]["wq"])    # (G, ...)
pg_full = pg.reshape(rg.shape)
np.testing.assert_allclose(pg_full, rg, rtol=2e-4, atol=1e-6)
# embed grads live on stage 0
pe = np.asarray(pipe_grads["embed"]["table"])[0]
re = np.asarray(ref_grads["embed"]["table"])
np.testing.assert_allclose(pe, re, rtol=2e-4, atol=1e-6)
print("OK", got, ref)
"""


def test_two_stage_pipeline_matches_sequential():
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, (r.stdout[-1500:], r.stderr[-3000:])
    assert "OK" in r.stdout
