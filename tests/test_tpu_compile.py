"""Compile the Pallas kernels and one serving step for a described TPU v5e.

Nothing runs: each program is lowered and compiled by the TPU compiler for
a chip described by `get_topology_desc`, at the serving widths of the
one-chip deployment (d=100, c=2500 partitions, m=25 PQ subspaces,
128-query tiles, 1M vectors). This catches what interpret mode cannot —
Mosaic lowering refusals, unaligned slices, scoped-VMEM overflows — at no
chip time. The topology is described inside a fixture, never at import.
"""
import functools
import math
import os
import re

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
    from repro.kernels.pq_score import (code_table_shape, pq_score_pallas,
                                        pq_score_window_pallas)
    from repro.kernels.soar_assign import soar_assign_pallas
    from repro.kernels.tree_route import tree_route_pallas
    from repro.kernels.vq_assign import vq_assign_pallas
    f32, i32 = jnp.float32, jnp.int32
    s = int(round(C ** 0.5))                  # TreeRouter default supers
    return {
        "pq_score_window": (
            functools.partial(pq_score_window_pallas, interpret=False),
            ((BQ, M, 16), f32), (code_table_shape(C, PMAX, M), i32),
            ((BQ, 32), i32)),
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
    from repro.kernels.pq_score import code_table_shape
    from repro.quant.pq import PQCodebook
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    packed = search.PackedIVF(
        sds((C, D), jnp.float32), sds((C, PMAX), jnp.int32),
        sds(code_table_shape(C, PMAX, M), jnp.int32), None,
        sds((C,), jnp.int32),
        PQCodebook(sds((M, 16, D // M), jnp.float32)),
        sds(search.rerank_table_shape(N, D), jnp.float32))
    txt = search.search_jit_batched.lower(
        packed, sds((1024, D), jnp.float32), top_t=32, final_k=10,
        rerank_budget=256, bq=BQ, multiplicity=2).compile().as_text()
    assert "tpu_custom_call" in txt
    assert "pq_score_window_pallas" in txt


# glove-100-angular as one index on one chip (benchmarks/chip/configs):
# vectors, width, partitions, partition capacity, PQ subspaces.
GN, GD, GC, GCAP, GM = 1_183_514, 100, 2_959, 1_678, 25


@pytest.fixture(scope="module", params=[32, 128], ids=lambda bq: f"bq{bq}")
def glove_step(request, one_chip):
    """The compiled entry computation of one `search_jit_batched` call
    at glove100's shapes for a described v5e, one compile per bq."""
    from repro.core import search
    from repro.kernels.pq_score import code_table_shape
    from repro.quant.pq import PQCodebook
    mp = pytest.MonkeyPatch()
    mp.setattr(jax, "default_backend", lambda: "tpu")

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    table = search.rerank_table_shape(GN, GD)
    packed = search.PackedIVF(
        sds((GC, GD), jnp.float32), sds((GC, GCAP), jnp.int32),
        sds(code_table_shape(GC, GCAP, GM), jnp.int32), None,
        sds((GC,), jnp.int32),
        PQCodebook(sds((GM, 16, GD // GM), jnp.float32)),
        sds(table, jnp.float32))
    try:
        txt = search.search_jit_batched.lower(
            packed, sds((request.param, GD), jnp.float32), top_t=16,
            final_k=10, rerank_budget=256, bq=request.param,
            multiplicity=2).compile().as_text()
    finally:
        mp.undo()
    return table, txt[txt.index("\nENTRY"):].split("\n}")[0]


def test_rerank_table_is_read_in_place_at_glove100(glove_step):
    """The rerank table is committed row-major, (8, 128) tiles, and no
    operation of the search program copies it whole: each call gathers
    its candidates' lane-rows from the table as it lies in HBM."""
    table, entry = glove_step
    shape = ",".join(map(str, table))
    param = re.search(
        r"%(packed_rerank[\w.]*) = f32\[" + shape + r"\]\{([^}]*)\} "
        r"parameter\(", entry)
    assert param, "rerank table is not a parameter of the entry computation"
    name, layout = param.groups()
    assert layout == "1,0:T(8,128)"
    size = math.prod(table)
    operand = re.compile(r"[(, ]%" + re.escape(name) + r"[,)]")
    copies = []
    for line in entry.splitlines():
        if not operand.search(line) or "parameter(" in line:
            continue
        result = re.search(r" = (.+?) [\w.-]+\(%", line).group(1)
        if any(math.prod(map(int, s.split(","))) >= size
               for s in re.findall(r"\[([\d,]+)\]", result)):
            copies.append(line.strip()[:160])
    assert not copies, copies


def _result_shapes(line):
    """Array shapes of the result of one HLO instruction line."""
    result = re.search(r" = (.+?) [\w.-]+\(", line)
    return ([tuple(map(int, s.split(",")))
             for s in re.findall(r"\[([\d,]+)\]", result.group(1))]
            if result else [])


def test_code_table_is_read_in_place_at_glove100(glove_step):
    """The code table is committed row-major, (8, 128) tiles, and only the
    window kernel reads it: no operation takes the table and makes an
    array of its size, and no window of codes — (bq, t·pmax, m),
    (m, bq, cand) or a gathered (·, pmax, m) slab — is made at all."""
    from repro.kernels.pq_score import code_table_shape
    _, entry = glove_step
    table = code_table_shape(GC, GCAP, GM)
    param = re.search(
        r"%(packed_part_codes[\w.]*) = s32\[" + ",".join(map(str, table))
        + r"\]\{([^}]*)\} parameter\(", entry)
    assert param, "code table is not a parameter of the entry computation"
    name, layout = param.groups()
    assert layout == "2,1,0:T(8,128)"
    operand = re.compile(r"[(, ]%" + re.escape(name) + r"[,)]")
    readers, copies, windows = [], [], []
    for line in entry.splitlines():
        if "parameter(" in line:
            continue
        shapes = _result_shapes(line)
        if operand.search(line):
            readers.append(line.strip()[:160])
            if any(math.prod(s) >= math.prod(table) for s in shapes):
                copies.append(line.strip()[:160])
        if any(GM in s and max(s) >= GCAP for s in shapes):
            windows.append(line.strip()[:160])
    assert not copies, copies
    assert not windows, windows
    assert len(readers) == 1 and "pq_score_window_pallas" in readers[0], \
        readers
