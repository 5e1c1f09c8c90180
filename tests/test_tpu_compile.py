"""Compile the Pallas kernels and one serving step for a described TPU v5e.

Nothing runs: each program is lowered and compiled by the TPU compiler for
a chip described by `get_topology_desc`, at the serving widths of the
one-chip deployment (d=100, c=2500 partitions, m=25 PQ subspaces,
128-query tiles, 1M vectors). This catches what interpret mode cannot —
Mosaic lowering refusals, unaligned slices, scoped-VMEM overflows — at no
chip time. The topology is described inside a fixture, never at import.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

D, C, M, BQ, N, PMAX = 100, 2500, 25, 128, 1_000_000, 2048


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _kernel_cases():
    from repro.kernels.lloyd import lloyd_sweep_pallas
    from repro.kernels.pq_score import pq_score_pallas, pq_score_window_pallas
    from repro.kernels.soar_assign import soar_assign_pallas
    from repro.kernels.tree_route import tree_route_pallas
    from repro.kernels.vq_assign import vq_assign_pallas
    f32, i32, u8 = jnp.float32, jnp.int32, jnp.uint8
    s = int(round(C ** 0.5))                  # TreeRouter default supers
    return {
        "pq_score_window": (
            functools.partial(pq_score_window_pallas, interpret=False),
            ((BQ, M, 16), f32), ((BQ, 32 * 1000, M), u8)),
        "pq_score": (functools.partial(pq_score_pallas, interpret=False),
                     ((BQ, M, 16), f32), ((65_536, M), i32)),
        "vq_assign": (functools.partial(vq_assign_pallas, interpret=False),
                      ((65_536, D), f32), ((C, D), f32)),
        "soar_assign": (
            functools.partial(soar_assign_pallas, interpret=False),
            ((65_536, D), f32), ((65_536, D), f32), ((65_536,), i32),
            ((C, D), f32)),
        "lloyd_sweep": (
            functools.partial(lloyd_sweep_pallas, c=C, interpret=False),
            ((131_072, D), f32), ((C, D), f32)),
        "tree_route": (
            functools.partial(tree_route_pallas, t_route=-(-s // 8),
                              interpret=False),
            ((BQ, D), f32), ((s, D), f32), ((s, 128, D), f32),
            ((s, 128), i32)),
    }


@pytest.mark.parametrize("name", ["pq_score_window", "pq_score", "vq_assign",
                                  "soar_assign", "lloyd_sweep", "tree_route"])
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, *shapes = _kernel_cases()[name]
    assert "tpu_custom_call" in _compile(fn, one_chip, *shapes)


def test_serving_step_compiles_for_v5e(one_chip, monkeypatch):
    """One whole `search_jit_batched` tile at 1M-vector PackedIVF shapes:
    the window-scoring kernel must be in the program as a Mosaic call.
    The backend branches of core/search.py are steered to their TPU side
    here, since the process itself runs on the CPU."""
    from repro.core import search
    from repro.quant.pq import PQCodebook
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    packed = search.PackedIVF(
        sds((C, D), jnp.float32), sds((C, PMAX), jnp.int32),
        sds((C, PMAX, M), jnp.uint8), None, sds((C,), jnp.int32),
        PQCodebook(sds((M, 16, D // M), jnp.float32)),
        sds((N, D), jnp.float32))
    txt = search.search_jit_batched.lower(
        packed, sds((1024, D), jnp.float32), top_t=32, final_k=10,
        rerank_budget=256, bq=BQ, multiplicity=2).compile().as_text()
    assert "tpu_custom_call" in txt
    assert "pq_score_window_pallas" in txt
