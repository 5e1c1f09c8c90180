"""chip_smoke.py's phases at a tiny size on the CPU.

The one-chip phases run with the backend branches steered to their TPU
side and every Pallas kernel in interpret mode, so the build kernels, the
window-scoring kernel, the frontend checks and the exact reference all
run. The four-chip phases run in a subprocess on four virtual CPU
devices. `main()` itself must refuse any backend that is not a TPU.
"""
import importlib.util
import os
import subprocess
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = dict(n=6000, d=16, c=15, m=4, nq=64, top_t=6, n_single=24, clients=4,
            n_sub=32, n_add=64, train_sample=4096, shard_size=2048)


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod       # dataclasses resolve it by name
    spec.loader.exec_module(mod)
    return mod


def test_main_refuses_a_backend_without_tpu(smoke, capsys):
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


def test_exact_reference_and_recall(smoke):
    import numpy as np
    rng = np.random.default_rng(0)
    X = rng.standard_normal((5000, 8)).astype(np.float32)
    Q = rng.standard_normal((7, 8)).astype(np.float32)
    keep = rng.random(5000) < 0.3
    want = np.argsort(-np.where(keep, Q @ X.T, -np.inf), axis=1)[:, :10]
    got = smoke.exact_topk(X, Q, keep=keep, chunk=700)
    assert np.array_equal(got, want)
    assert smoke.recall_at_k(got, want) == 1.0
    assert smoke.recall_at_k(np.full_like(got, -1), want) == 0.0


def test_one_chip_phases_tiny_interpret(smoke, monkeypatch):
    from repro import kernels
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(kernels, "interpret_mode", lambda: True)
    out = smoke.one_chip(smoke.Size(**TINY), seed=0, require_kernels=False)
    for key in ("recall_single", "recall_bulk", "recall_tenant",
                "recall_after_add", "recall_after_remove"):
        assert out[key] >= smoke.RECALL_FLOOR, (key, out[key])
    routes = set(out["routes"])
    assert {("lloyd_sweep", "interpret"), ("assign_fused", "interpret"),
            ("pq_score_window", "interpret")} <= routes
    assert not any(r in ("mosaic", "xla") for _, r in routes), routes
    assert out["kernels"]["serve"] == set()   # interpret mode: no Mosaic


FOUR = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, ".")
import chip_smoke as cs
size = cs.Size(n=8000, d=16, c=20, m=4, nq=64, top_t=6, n_single=0,
               clients=1, n_sub=0, n_add=0, train_sample=4096,
               shard_size=2048)
out = cs.four_chips(size, seed=0)
assert out["recall_replica"] >= cs.RECALL_FLOOR, out
assert out["recall_sharded"] >= cs.RECALL_FLOOR, out
print("OK", out)
"""


def test_four_chip_phases_on_virtual_devices():
    r = subprocess.run([sys.executable, "-c", FOUR], capture_output=True,
                       text=True, cwd=ROOT,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-3000:])
    assert "OK" in r.stdout
    assert "replica over 4 devices" in r.stdout
