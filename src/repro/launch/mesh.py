"""Production meshes + logical→physical sharding rules.

IMPORTANT: importing this module never touches jax device state; meshes are
built inside functions only (so smoke tests see 1 CPU device while
dryrun.py, which sets XLA_FLAGS first, sees 512).
"""
from __future__ import annotations

import numpy as np

import jax


def make_mesh(shape, axes, devices=None):
    """`jax.make_mesh` with Auto axes. The logical rules below are hints
    that `with_sharding_constraint` hands to the partitioner; under the
    Explicit axes `jax.make_mesh` now defaults to, the same constraint is
    an assertion on the argument's sharding, and the model code fails."""
    from jax.sharding import AxisType
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes),
                         devices=devices)


def to_shardings(mesh, tree):
    """PartitionSpec pytree → NamedSharding pytree, for jit
    in_shardings/out_shardings without an ambient mesh. is_leaf guard:
    PartitionSpec is a tuple subclass, so tree.map would otherwise
    flatten into it.
    """
    from jax.sharding import NamedSharding, PartitionSpec
    return jax.tree.map(
        lambda x: NamedSharding(mesh, x) if isinstance(x, PartitionSpec) else x,
        tree, is_leaf=lambda x: isinstance(x, PartitionSpec))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devs = jax.devices()
    if len(devs) == n:
        return make_mesh(shape, axes)
    assert len(devs) >= n, (
        f"need {n} devices for mesh {shape}; have {len(devs)} — dryrun.py "
        f"must set XLA_FLAGS=--xla_force_host_platform_device_count=512 "
        f"before any jax import")
    return make_mesh(shape, axes, devices=devs[:n])


def make_test_mesh(shape=(2, 4), axes=("data", "model")):
    """Small mesh for subprocess-based distribution tests."""
    n = int(np.prod(shape))
    return make_mesh(shape, axes, devices=jax.devices()[:n])


# --------------------------------------------------------------------------
# Logical axis rules (DESIGN.md §6)
# --------------------------------------------------------------------------

BASE_RULES = {
    # parameters: FSDP over "data" on the embed dim, TP over "model"
    "embed": "data",
    "mlp": "model",
    "heads": "model",
    "head": None,
    "kv_heads": None,
    "vocab": "model",
    "expert": "model",
    "expert_mlp": None,
    "shead": "model",     # sLSTM (head × block) sub-heads
    # activations
    "batch": "data",
    "act_embed": None,
    "kv_seq": "model",
}


def build_rules(arch_overrides: dict | None = None, *, multi_pod: bool = False,
                batch_size: int | None = None, dp_degree: int = 16) -> dict:
    """Resolve the rule set for one (arch × shape × mesh) cell.

    - multi-pod: batch additionally shards over the outer "pod" axis.
    - batch=1 cells (long_500k): batch unshardable → the KV seq dim takes
      ALL mesh axes instead (524288/512 = 1024 rows per chip).
    """
    rules = dict(BASE_RULES)
    if multi_pod:
        rules["batch"] = ("pod", "data")
    if arch_overrides:
        rules.update(arch_overrides)
    if batch_size is not None:
        dp = dp_degree * (2 if multi_pod else 1)
        if batch_size < dp:
            rules["batch"] = None
            rules["kv_seq"] = (("pod", "data", "model") if multi_pod
                               else ("data", "model"))
    return rules
